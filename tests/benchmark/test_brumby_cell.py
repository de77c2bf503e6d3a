"""The power-retention cell (``brumby-serve-doc16``): its configuration
against the catalog's published numbers, the byte and operation
arithmetic of ``shapes_retention``, its three readers on a synthetic slice
and on slices that lack what they read, the runner ``serve_model`` end to
end on a tiny configuration of the same family, its check failing a
planted fault, and the cell's entry with the lists it joined (by
membership, so that a later cell turns nothing red)."""

import copy
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
for path in (ROOT, HERE):    # the program; _spec.py beside this file
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from _spec import check_cells, check_entry, entry  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.lib import shapes_retention  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "brumby-serve-doc16"
CONFIG = "brumby-14b-base-l8"
SOURCE = ("https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
          "config.json")
# the catalog entry's config (the published config.json)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
# the readers of the retention, entries of BENCHMARK.json that list the
# cell: better
NEW = {"decode_retention_share": "lower",
       "decode_retention_hbm_util": "higher"}
# ... and one that is not an entry: a 5 s slice of the cell may hold no
# prefill chunk, and a listed metric that reads nothing refuses a run
# (PERF.md, section 3)
READERS = [*NEW, "prefill_retention_share"]
# itl_p95_ms and what moves it. Not serve_tok_s nor what moves it: a 45 s
# window holds a handful of the cell's completions (PERF.md, section 2).
JOINED = ["itl_p95_ms", "serve_device_idle_share",
          "decode_device_ms_per_step", "serve_dry_step_share"]


def _config() -> dict:
    return json.loads((ROOT / "benchmark/configs" / f"{CONFIG}.json")
                      .read_text())


# ---- the configuration ------------------------------------------------------------

def test_the_configuration_keeps_every_published_number():
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    cfg = _config()
    assert entry["source"] == SOURCE == cfg["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key
    assert cfg["published"] == {
        key: PUBLISHED[key] for key in entry["reduced"]}


def test_the_cut_and_what_the_file_states_besides():
    cfg = _config()
    assert cfg["num_hidden_layers"] == 8 and cfg["family"] == "brumby"
    assert cfg["deployment"]["pipeline_stages"] == 5
    assert cfg["deployment"]["this_chip"] == {"stage": 0, "layers": [0, 8]}
    for key in ("degree", "gate", "q_norm_k_norm", "scale", "eps",
                "state_dtype", "init", "serve"):
        assert key in cfg["assumed"], key
    assert cfg["power_retention"] == {
        "degree": 2, "gate": "per_kv_head", "eps": 1e-6,
        "state_dtype": "float32"}
    assert cfg["departures"] == [] and cfg["departures_why"]
    assert cfg["serve"] == {"param_dtype": "bfloat16", "num_slots": 16,
                            "max_seq_len": 32768, "block_size": 16,
                            "prefill_chunk": 512}


def test_the_family_builds_the_published_widths():
    from benchmark.lib.families import brumby

    cfg = _config()
    m = brumby.build(cfg, max_seq_len=64)
    assert (m.d_model, m.num_heads, m.kv_heads, m.head_dim, m.d_ff) == (
        5120, 40, 8, 128, 17408)
    assert (m.vocab_size, m.rope_theta, m.norm_eps) == (151936, 1e6, 1e-6)
    assert m.mixers == ("power_retention",) * 8 and m.feature_dim == 8256
    assert m.retention_eps == 1e-6
    for key, value in (("degree", 3), ("state_dtype", "bfloat16")):
        with pytest.raises(ValueError, match="degree-2 float32"):
            brumby.build(dict(cfg, power_retention=dict(
                cfg["power_retention"], **{key: value})))


# ---- bytes and operations ---------------------------------------------------------

def test_shape_arithmetic_of_the_retention_model():
    cfg = _config()
    s = shapes_retention
    assert s.features(cfg) == 8256
    # Wq 26.21M, Wk 5.24M, Wv 5.24M, Wo 26.21M, gate 0.04M, MLP 267.39M,
    # norms 0.01M
    assert s.layer_params(cfg) == (26_214_400 + 2 * 5_242_880 + 26_214_400
                                   + 5121 * 8 + 267_386_880 + 2 * 128
                                   + 2 * 5120)
    assert s.layer_params(cfg) == pytest.approx(330.35e6, rel=1e-5)
    assert 8 * 2 * s.layer_params(cfg) == pytest.approx(5.286e9, rel=1e-3)
    assert 2 * 2 * 151_936 * 5120 == pytest.approx(3.112e9, rel=1e-3)
    assert s.weight_bytes(cfg) == pytest.approx(8.40e9, rel=2e-3)
    # S 8 x 8,256 x 128 x 4 B and z 8 x 8,256 x 4 B
    assert s.state_bytes(cfg) == 8 * 8256 * 128 * 4 + 8 * 8256 * 4
    assert s.state_bytes(cfg) == pytest.approx(34.08e6, rel=1e-4)
    assert s.state_pool_bytes(cfg) == pytest.approx(4.36e9, rel=1e-3)
    # about 12.76 GB of arguments, 80% of the chip's 16 GB
    args = s.weight_bytes(cfg) + s.state_pool_bytes(cfg)
    assert args == pytest.approx(12.76e9, rel=1e-3)
    assert 0.79 < args / 16e9 < 0.80
    # the state equals a K/V cache of 4 KB a position a layer at 8.3k
    assert s.kv_bytes_per_position(cfg) == 4096
    assert s.state_bytes(cfg) / 4096 == pytest.approx(8320, rel=1e-3)
    # a decode step of 16 rows, the per-token form: 5.29 GB of layers,
    # 1.56 of head, the state read and written (8.72): 15.6 GB, 19.0 ms
    assert s.state_read_bytes(cfg, 16) == pytest.approx(4.36e9, rel=1e-3)
    step = s.decode_step_bytes(cfg, 16)
    assert step == pytest.approx(15.57e9, rel=1e-3)
    assert 2 * s.state_read_bytes(cfg, 16) / step == pytest.approx(0.56,
                                                                   abs=0.01)
    assert step / 819e9 * 1e3 == pytest.approx(19.0, abs=0.05)
    # a prefill chunk of 512, a layer: 338 GFLOP of projections and MLP,
    # 43.6 of phi(Q)^T S0 (43.3 of it S0's), 8.7 of the update, 5.4 of
    # the quadratic part
    flops = s.prefill_flops(cfg, 512)
    assert flops["matmul"] == pytest.approx(338.3e9, rel=1e-3)
    assert flops["carry"] == pytest.approx(43.6e9, rel=1e-3)
    assert 2 * 512 * 40 * 8256 * 128 == pytest.approx(43.3e9, rel=1e-3)
    assert flops["update"] == pytest.approx(8.72e9, rel=1e-3)
    assert flops["chunk"] == pytest.approx(5.37e9, rel=1e-3)
    assert 8 * sum(flops.values()) == pytest.approx(3.16e12, rel=1e-2)


# ---- the readers ----------------------------------------------------------------

MS = 1_000_000
TALLY = "tpu_ddp.serve.tally"


def _program(with_scopes=True, with_counts=True):
    """Two decode executions of 30 ms: 12 ms under retention/state, 3
    elsewhere in retention, 9 in the MLP, 4 in the head, 2 bare; one
    prefill of 60 ms, 20 under retention/carry, 4 under retention/chunk,
    6 elsewhere in retention, 30 in the MLP. Tallies 10 decode steps
    apart that decoded 15 rows each."""
    dev = "/device:TPU:0"
    scopes, programs = [], []
    for t0 in (100 * MS, 200 * MS):
        programs.append(["serve_decode", t0, 30 * MS])
        t = t0
        for path, d in (("jit(serve_decode)/retention/state/dot_general", 8),
                        ("jit(serve_decode)/retention/state/fusion", 4),
                        ("jit(serve_decode)/retention/dot_general", 3),
                        ("jit(serve_decode)/mlp/dot_general", 9),
                        ("jit(serve_decode)/head/dot_general", 4),
                        ("", 2)):
            if not with_scopes:
                path = path.replace("/retention", "").replace("/state", "")
            scopes.append([path, t, d * MS])
            t += d * MS
    programs.append(["serve_prefill", 300 * MS, 60 * MS])
    t = 300 * MS
    for path, d in (("jit(serve_prefill)/retention/carry/dot_general", 20),
                    ("jit(serve_prefill)/retention/chunk/dot_general", 4),
                    ("jit(serve_prefill)/retention/dot_general", 6),
                    ("jit(serve_prefill)/mlp/dot_general", 30)):
        if not with_scopes:
            path = path.replace("/retention", "").replace(
                "/carry", "").replace("/chunk", "")
        scopes.append([path, t, d * MS])
        t += d * MS

    def tally(start, k):
        counts = {"steps": k, "context_tokens": 17_000 * 15 * k}
        if with_counts:
            counts.update(decode_steps=k, decode_rows=15 * k)
        return [TALLY, start, 0, counts]

    return {"spans": [tally(50 * MS, 100), tally(400 * MS, 110)],
            "programs": {dev: programs}, "scopes": {dev: scopes}}


def _record(program, config=None):
    return SimpleNamespace(
        trace={"devices": {}, "host": []}, window=(0, 10_000 * MS),
        counters={}, values={}, cell={}, config=config or _config(),
        traffic={}, device={"kind": "TPU v5 lite", "count": 1},
        program=program)


def _read(name, record):
    return run.load_module("layer_metrics", name).read(record)


def test_new_readers_against_hand_arithmetic():
    cfg = _config()
    rec = _record(_program())
    need = shapes_retention.state_read_bytes(cfg, 15)
    want = {"decode_retention_share": 100 * 15 / 30,
            "decode_retention_hbm_util": 100 * need / (819e9 * 12e-3),
            "prefill_retention_share": 100 * 30 / 60}
    for name in READERS:
        assert _read(name, rec) == pytest.approx(want[name]), name
    # the share cannot pass 100: the state read once, at 12 ms for 4.1 GB
    assert want["decode_retention_hbm_util"] < 100


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A dense cell's trace (the programs, no retention scopes, a tally
    without decode rows), an empty one, and a program of this model
    without the scopes and the counts."""
    dense = json.loads((ROOT / "benchmark/configs/starcoder2-3b.json")
                       .read_text())
    bare = _program(with_scopes=False, with_counts=False)
    for rec in (_record(bare, dense), _record(bare),
                _record({"spans": [], "programs": {}, "scopes": {}})):
        for name in READERS:
            assert _read(name, rec) is None, name
    # the hybrid's ssm/state is not this model's state
    assert _read("decode_retention_hbm_util", _record(_program(), dense)) \
        is None


def test_prefill_share_counts_no_loop_over_its_body():
    """The chunked form's parts run in ``while`` loops, whose events cover
    their bodies' and carry no scope: they count neither in the part nor
    in the whole. Here a loop of 24 ms over the 20 ms under
    retention/carry and the 4 under retention/chunk, and a loop inside
    the first (nested, 20 ms): still 30 of 60 ms."""
    prog = _program()
    t = 300 * MS
    prog["scopes"]["/device:TPU:0"] += [["", t, 24 * MS], ["", t, 20 * MS]]
    assert _read("prefill_retention_share", _record(prog)) \
        == pytest.approx(50.0)


# ---- the runner on a tiny configuration of the family ----------------------------

TINY_TRAFFIC = {"runner": "serve_model", "clients": 3, "warm_completions": 2,
                "requests": [[40, 6], [55, 9], [70, 12], [45, 7], [60, 5],
                             [50, 10]],
                # float32 weights, bf16 compute against the float32
                # reference: 0.006-0.01 at these widths
                "check": {"prompt_lens": [70, 12], "new_tokens": 4,
                          "logprob_tol": 0.05}}


def _tiny_config() -> dict:
    cfg = copy.deepcopy(_config())
    cfg.update(name="tiny-brumby", hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, vocab_size=512, num_hidden_layers=2,
               serve={"param_dtype": "float32", "num_slots": 3,
                      "max_seq_len": 512, "block_size": 4,
                      "prefill_chunk": 16})
    return cfg


def _serve_tiny(tmp_path, monkeypatch, capsys, traffic=TINY_TRAFFIC):
    """The runner on the tiny cell, a window of 1 s: (exit code, the
    detail line, the result line)."""
    import jax

    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic" / "tiny-doc.json").write_text(json.dumps(traffic))
    (tmp_path / "configs" / "tiny-brumby.json").write_text(
        json.dumps(_tiny_config()))
    spec = json.loads((HERE / "tiny" / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-brumby", "source": "test", "reduced": [],
        "file": str(tmp_path / "configs" / "tiny-brumby.json"),
        "why": "test"})
    spec["workloads"].append({"name": "retention-doc", "config": "tiny-brumby",
                              "traffic": "tiny-doc", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("serve_tok_s", "itl_p95_ms"):
            m["workloads"].append("retention-doc")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "SPEC", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(run, "SEARCH", run.SEARCH + [tmp_path])
    monkeypatch.setattr(run, "check_devices",
                        lambda chips: jax.devices()[:chips])
    rc = run.main(["--workload", "retention-doc", "--seed",
                   str(2 ** 31 + 91), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-2]), json.loads(out[-1])


def test_runner_serves_the_family_end_to_end(tmp_path, monkeypatch, capsys):
    rc, detail, line = _serve_tiny(tmp_path, monkeypatch, capsys)
    assert rc == 0 and line["correct"] is True, detail["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    assert detail["notes"]["logprob_check"]["max_abs_diff"] < 0.05


@pytest.mark.parametrize("fault", ["none", "zero_carry", "bf16_state",
                                   "reference_fp8"])
def test_the_check_fails_a_planted_fault(fault, tmp_path, monkeypatch,
                                         capsys):
    """The runner's own check (``serve_model._logprob_check`` against the
    traffic's ``logprob_tol``) on a first check prompt of 70 positions,
    five chunks, and 400 decoded tokens, with the engine computing in
    float32 so that nothing but the fault parts it from the reference
    (1e-6 as it is, so a limit of 0.01 here). Each makes the run not
    ``correct``: every chunk started from a zero state in place of the
    one the slot carries; ``S`` and ``z`` kept in bfloat16 (0.024: the
    rounding of a state builds up over the decoded tokens, 0.008 at 100
    of them); the reference with its weights rounded to float8 e4m3 as
    it uses them (the nearest floating precision below the
    configuration's bf16, a scale a column: ``reference.rounded``)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.families import brumby
    from benchmark.reference import brumby as reference
    from tpu_ddp.models.retention import RetentionLM
    from tpu_ddp.serve import engine as engine_mod

    build = brumby.build
    monkeypatch.setattr(
        brumby, "build",
        lambda config, **kw: build(config, compute_dtype=jnp.float32,
                                   **kw))
    if fault == "zero_carry":
        real = engine_mod.retention_state_chunk
        monkeypatch.setattr(
            engine_mod, "retention_state_chunk",
            lambda model, blk, x, state, si, slot, fresh, n_valid, pos:
            real(model, blk, x, state, si, slot, True, n_valid, pos))
    if fault == "bf16_state":
        shapes = RetentionLM.state_shapes
        monkeypatch.setattr(
            RetentionLM, "state_shapes",
            lambda self, n: {k: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                             for k, s in shapes(self, n).items()})
    if fault == "reference_fp8":
        log_probs = reference.log_probs
        monkeypatch.setattr(
            reference, "log_probs",
            lambda p, tokens, config: log_probs(p, tokens, config,
                                                fmt="fp8"))
    engine_mod._build_decode_step.cache_clear()
    engine_mod._build_prefill_step.cache_clear()
    check = dict(TINY_TRAFFIC["check"], logprob_tol=0.01, new_tokens=400)
    try:
        rc, detail, line = _serve_tiny(tmp_path, monkeypatch, capsys,
                                       dict(TINY_TRAFFIC, check=check))
    finally:
        engine_mod._build_decode_step.cache_clear()
        engine_mod._build_prefill_step.cache_clear()
    diff = detail["notes"]["logprob_check"]["max_abs_diff"]
    assert line["attempted"] > 0 and line["failed"] == 0
    if fault == "none":
        assert rc == 0 and line["correct"] is True and diff < 1e-3, diff
    else:
        assert line["correct"] is False and diff > 0.01, diff
        assert detail["notes"]["checks"] == {"all_requests_ok": True,
                                             "logprobs_agree": False}


# ---- the cell and the lists it joined ---------------------------------------------

def check_spec(spec):
    """The cell's own entries and the lists it joined, by name and by
    membership, so that a later entry or cell changes nothing here."""
    for name, better in NEW.items():
        check_entry(spec, name, [CELL], unit="%", better=better,
                    source="device_trace", layer="kernels",
                    moves="itl_p95_ms")
    for name in JOINED:
        check_cells(entry(spec, name), [CELL])
    # what moves serve_tok_s does not list the cell, which does not
    # report it
    for m in spec["per_layer"]:
        if m["moves"] == "serve_tok_s":
            assert CELL not in m.get("workloads", [CELL]), m["name"]
    assert CELL not in entry(spec, "serve_tok_s")["workloads"]


def test_the_cell_and_the_lists_it_joined():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "serve-doc16", 1)
    check_spec(SPEC)
    for name in READERS:
        assert run.find("layer_metrics", name, ".py").is_file()


def test_the_traffic_is_a_fixed_list_of_long_documents():
    traffic = json.loads((ROOT / "benchmark/traffic/serve-doc16.json")
                         .read_text())
    pairs = traffic["requests"]
    assert traffic["runner"] == "serve_model" and traffic["clients"] == 16
    assert traffic["warm_completions"] >= 16 and len(pairs) == 64
    prompts, outputs = sorted(p for p, _ in pairs), sorted(o for _, o in pairs)
    assert 8192 <= prompts[0] and prompts[-1] <= 28672
    assert 1024 <= outputs[0] and outputs[-1] <= 4096
    assert 15_000 < prompts[32] < 16_000 and 1_900 < outputs[32] < 2_200
    # no request passes the published context
    assert max(p + o for p, o in pairs) <= _config()["max_position_embeddings"]
    assert _config()["serve"]["max_seq_len"] == 32768
    # ten chunks carried, then 8 tokens through the one-token form
    assert traffic["check"]["prompt_lens"] == [5000, 300]
    assert traffic["check"]["new_tokens"] == 8
