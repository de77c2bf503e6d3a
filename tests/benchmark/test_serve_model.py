"""Runner ``serve_model`` end to end on a tiny hybrid cell and a tiny dense
one (their own spec in ``tmp_path``), and the readers and byte functions
that came with the hybrid cell against hand arithmetic."""

import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import run  # noqa: E402
from benchmark.lib import scope_time, shapes_hybrid  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "gr4h-serve-chat"
NEW = ["hybrid_decode_device_ms_per_step",
       "hybrid_prefill_device_ms_per_chunk", "decode_ssm_share",
       "decode_moe_share", "decode_ssm_hbm_util", "decode_moe_hbm_util",
       "hybrid_decode_hbm_util", "prefill_ssm_share"]

TINY_HYBRID = {
    "name": "tiny-hybrid", "family": "granite_hybrid",
    "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_local_experts": 4, "held_experts": [0, 4],
    "published": {"num_hidden_layers": 8, "num_local_experts": 8},
    "num_experts_per_tok": 3, "mamba_n_heads": 4, "mamba_d_head": 32,
    "mamba_expand": 2, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_n_groups": 1, "mamba_chunk_size": 8,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0625, "logits_scaling": 4,
    "rms_norm_eps": 1e-5,
    "serve": {"param_dtype": "float32", "num_slots": 4, "max_seq_len": 128,
              "block_size": 8, "prefill_chunk": 16}}
TRAFFIC = {"runner": "serve_model", "clients": 4, "warm_completions": 2,
           "requests": [[10, 6], [20, 9], [33, 12], [17, 7], [40, 5],
                        [12, 10]],
           "check": {"prompt_lens": [21, 9], "new_tokens": 4,
                     "logprob_tol": 0.05}}


@pytest.fixture
def cells(tmp_path, monkeypatch):
    """run.py pointed at two cells of this test's own, any device let in."""
    import jax

    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic" / "tiny-chat.json").write_text(
        json.dumps(TRAFFIC))
    (tmp_path / "configs" / "tiny-hybrid.json").write_text(
        json.dumps(TINY_HYBRID))
    spec = json.loads((HERE / "tiny" / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-hybrid", "source": "test", "reduced": [],
        "file": str(tmp_path / "configs" / "tiny-hybrid.json"),
        "why": "test"})
    for name, config in (("hybrid-chat", "tiny-hybrid"),
                         ("dense-chat", "tiny-lm")):
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": "tiny-chat", "chips": 1,
                                  "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("serve_tok_s", "itl_p95_ms"):
            m["workloads"] += ["hybrid-chat", "dense-chat"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "SPEC", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(run, "SEARCH", run.SEARCH + [tmp_path])
    monkeypatch.setattr(run, "check_devices",
                        lambda chips: jax.devices()[:chips])
    return run


@pytest.mark.parametrize("cell", ["hybrid-chat", "dense-chat"])
def test_runner_end_to_end(cells, capsys, cell):
    rc = cells.main(["--workload", cell, "--seed", str(2 ** 31 + 29),
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    detail, line = json.loads(out[-2]), json.loads(out[-1])
    assert rc == 0 and line["correct"] is True, detail["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    # float32 weights, bf16 compute: well inside the tolerance
    assert detail["notes"]["logprob_check"]["max_abs_diff"] < 0.02
    assert detail["counters"]["compiles_in_window"] == 0
    # what a decode step read, from the engine's gauges: every step's
    assert 0 < detail["counters"]["decode_rows_mean"] <= 4
    assert detail["counters"]["decode_context_tokens_mean"] > 0


def test_a_config_names_its_family_or_is_the_dense_one():
    real = json.loads((ROOT / "benchmark/configs/"
                       "granite-4.0-h-small-l10e36.json").read_text())
    assert real["family"] == "granite_hybrid"
    for name in ("granite_hybrid", "gpt"):
        assert (ROOT / f"benchmark/lib/families/{name}.py").is_file()
        assert (ROOT / f"benchmark/reference/{name}.py").is_file()
    from benchmark.lib.families import granite_hybrid
    model = granite_hybrid.build(TINY_HYBRID, max_seq_len=32)
    assert model.num_experts == 8 and model.held == (0, 4)
    assert model.mixers == tuple(TINY_HYBRID["layer_types"])
    with pytest.raises(ValueError, match="held_experts"):
        granite_hybrid.build(dict(TINY_HYBRID, num_local_experts=3))


# ---- the configuration and the cell, as BENCHMARK.json has them ---------------

def test_the_configuration_keeps_every_published_number():
    rows = [json.loads(ln) for ln in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if Path("/opt/skills/guides/model-configs/"
                "architectures.jsonl").is_file() else []
    row = next((r for r in rows if r["name"] == "granite-4.0-h-small"),
               None)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    entry = next(c for c in SPEC["configs"]
                 if c["name"] == "granite-4.0-h-small-l10e36")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_local_experts"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            continue
        want = value[:10] if key == "layer_types" else value
        assert cfg[key] == want, key
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 10
    assert cfg["num_local_experts"] == 36
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_local_experts": 72}
    assert {"assumed", "departures", "deployment"} <= set(cfg)


def test_the_cell_and_the_lists_it_joined():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small-l10e36", "serve-chat64", 1)
    joined = {m["name"] for sec in ("end_to_end", "per_layer")
              for m in SPEC[sec] if CELL in m.get("workloads", [])}
    assert joined == {"serve_tok_s", "itl_p95_ms", "slot_occupancy_mean",
                      "serve_device_idle_share", "serve_peak_hbm_gb",
                      *NEW}
    for name in NEW:
        m = next(m for m in SPEC["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
        assert run.find("layer_metrics", name, ".py").is_file()
    traffic = json.loads((ROOT / "benchmark/traffic/serve-chat64.json")
                         .read_text())
    pairs = traffic["requests"]
    assert traffic["clients"] == 64 and len(pairs) == 64
    assert 64 <= min(p for p, _ in pairs) and max(p for p, _ in pairs) <= 768
    assert 64 <= min(o for _, o in pairs) and max(o for _, o in pairs) <= 384
    assert traffic["check"]["prompt_lens"] == [300, 64]


# ---- bytes --------------------------------------------------------------------

def test_shape_arithmetic_of_the_hybrid():
    cfg = json.loads((ROOT / "benchmark/configs/"
                      "granite-4.0-h-small-l10e36.json").read_text())
    s = shapes_hybrid
    assert (s.layers(cfg, "mamba"), s.layers(cfg, "attention")) == (9, 1)
    # in_proj 4096 x 16,768, out_proj 8192 x 4096, conv 5 x 8,448, ...
    assert s.ssm_mixer_params(cfg) == (
        4096 * 16768 + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192 + 4096)
    assert s.attn_mixer_params(cfg) == 4096 * 48 * 128 + 4096 * 4096 + 4096
    # router 72 wide, shared 3 x 4096 x 1536, 36 experts of 9.44M
    assert s.moe_params(cfg) == (4096 * 72 + 3 * 4096 * 1536
                                 + 36 * 3 * 4096 * 768 + 4096)
    # 128 x 64 x 128 float32 and a 3 x 8,448 bf16 tail, nine layers
    assert s.state_bytes_per_slot(cfg) == 9 * (4 * 1048576 + 2 * 3 * 8448)
    assert s.kv_bytes_per_token(cfg) == 2 * 1 * 8 * 128 * 2
    assert s.weight_bytes(cfg) == pytest.approx(9.93e9, rel=2e-3)
    assert s.moe_step_bytes(cfg) == 10 * 2 * s.moe_params(cfg)
    assert s.ssm_step_bytes(cfg, 10) == (
        9 * 2 * s.ssm_mixer_params(cfg)
        + 2 * 10 * s.state_bytes_per_slot(cfg))
    assert s.decode_step_bytes(cfg, 64, 20000) == (
        s.weight_bytes(cfg) + 128 * s.state_bytes_per_slot(cfg)
        + 20000 * 4096)
    # Motivation's budget: 14.9 GB a step at 64 slots
    assert s.decode_step_bytes(cfg, 64, 64 * 300) == pytest.approx(
        14.9e9, rel=5e-3)


# ---- the readers on a toy trace -----------------------------------------------

def _reader(name):
    path = ROOT / "benchmark" / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"lm_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _record(program, config=None):
    cfg = config or json.loads(
        (ROOT / "benchmark/configs/granite-4.0-h-small-l10e36.json")
        .read_text())
    return SimpleNamespace(
        trace={"devices": {}, "host": []}, window=(0, 10_000_000_000),
        counters={}, values={}, cell={}, config=cfg, traffic={},
        device={"kind": "TPU v5 lite", "count": 1}, program=program)


def _toy_program():
    """Two decode executions of 20 ms and one prefill of 40 ms. In each
    decode: 8 ms under ssm (2 of them under ssm/state), 6 ms of the
    expert layer (1 under mlp/moe/route, 1 under mlp/moe/experts, 4 in
    the grouped products' custom call, which carries its own name and no
    scope), 2 ms under mlp/shared_mlp, 3 ms under attn, 1 ms bare."""
    ms = 1_000_000
    dev = "/device:TPU:0"
    scopes, programs, spans = [], [], []
    for k, t0 in enumerate((100 * ms, 200 * ms)):
        programs.append(["serve_decode", t0, 20 * ms])
        t = t0
        for path, d in (("jit(serve_decode)/ssm/dot_general", 6),
                        ("jit(serve_decode)/ssm/state/select_n", 2),
                        ("ragged-dot-none", 4),
                        ("jit(serve_decode)/mlp/moe/experts/gather", 1),
                        ("jit(serve_decode)/mlp/moe/route/sort", 1),
                        ("jit(serve_decode)/mlp/shared_mlp/dot_general", 2),
                        ("jit(serve_decode)/attn/dot_general", 3),
                        ("", 1)):
            scopes.append([path, t, d * ms])
            t += d * ms
        spans.append(["tpu_ddp.serve.decode", t0 - 2 * ms, 10 * ms,
                      {"slots": 60 + 4 * k, "context_tokens": 20000,
                       "ahead": 1, "state_slots": 60 + 4 * k}])
    programs.append(["serve_prefill", 300 * ms, 40 * ms])
    scopes += [["jit(serve_prefill)/ssm/dot_general", 300 * ms, 10 * ms],
               ["ragged-dot-none", 310 * ms, 30 * ms]]
    return {"spans": spans, "programs": {dev: programs},
            "scopes": {dev: scopes}}


def _expected(cfg, steps):
    """What the readers should give for decode ``steps`` of 20 ms:
    ``[(state_slots, context_tokens), ...]``."""
    s, peak = shapes_hybrid, 819e9
    slots = sum(n for n, _ in steps) / len(steps)
    return {
        "hybrid_decode_device_ms_per_step": 20.0,
        "hybrid_prefill_device_ms_per_chunk": 40.0,
        "decode_ssm_share": 100 * 8 / 20,
        "decode_moe_share": 100 * 6 / 20,
        "prefill_ssm_share": 100 * 10 / 40,
        "decode_ssm_hbm_util":
        100 * s.ssm_step_bytes(cfg, slots) / (peak * 8e-3),
        "decode_moe_hbm_util": 100 * s.moe_step_bytes(cfg) / (peak * 8e-3),
        "hybrid_decode_hbm_util": 100 * sum(
            s.decode_step_bytes(cfg, n, ctx) / (peak * 20e-3)
            for n, ctx in steps) / len(steps)}


def test_new_readers_against_hand_arithmetic():
    rec = _record(_toy_program())
    want = _expected(rec.config, [(60, 20000), (64, 20000)])
    for name in NEW:
        assert _reader(name)(rec) == pytest.approx(want[name]), name
    assert scope_time.per_run_ms(rec, ("state",), "serve_decode") \
        == pytest.approx((2.0, 20.0))


def test_a_slice_without_an_annotated_step_reads_the_windows_means():
    """The engine annotates 24 steps of every 240 and this cell runs 20
    a second: most 5 s slices hold no ``serve.decode`` span. The readers
    then take what a step read from the runner's counters."""
    prog = _toy_program()
    prog["spans"] = []
    rec = _record(prog)
    rec.counters.update(decode_rows_mean=61.5,
                        decode_context_tokens_mean=21000.0)
    want = _expected(rec.config, [(61.5, 21000.0)])
    for name in NEW:
        assert _reader(name)(rec) == pytest.approx(want[name]), name


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent's trace of a dense cell: programs and spans, none of
    the new scopes, no ``state_slots`` on a span, a dense configuration."""
    prog = _toy_program()
    prog["scopes"] = {dev: [[p.replace("ssm", "attn").replace(
        "moe", "mlp").replace("shared_mlp", "mlp").replace(
        "ragged-dot-none", "jit(serve_decode)/mlp/dot_general"), s, d]
        for p, s, d in ev] for dev, ev in prog["scopes"].items()}
    for span in prog["spans"]:
        del span[3]["state_slots"]
    dense = json.loads((ROOT / "benchmark/configs/starcoder2-3b.json")
                       .read_text())
    for rec in (_record(prog, dense),
                _record({"spans": [], "programs": {}, "scopes": {}})):
        for name in NEW:
            assert _reader(name)(rec) is None, name
