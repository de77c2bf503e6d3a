"""The learned-sparse-attention cell (``keye-serve-long16``): its
configuration against the catalog's published numbers, the byte
arithmetic of ``shapes_sparse``, its four readers on a synthetic slice and
on slices that lack what they read, the runner ``serve_model`` end to end
on a tiny configuration of the same family, its check failing a planted
fault, and the cell's entry with the lists it joined (by membership, so
that a later cell turns nothing red)."""

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
from benchmark.lib import shapes_sparse  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "keye-serve-long16"
CONFIG = "keye-vl-2.0-30b-a3b-l8e16"
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")
# the language model's keys of the published config.json
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
# the readers of the indexer and the selection, entries of BENCHMARK.json
# that list the cell: (layer, source, better)
NEW = {"decode_index_share": ("kernels", "device_trace", "lower"),
       "decode_sparse_attn_share": ("kernels", "device_trace", "lower"),
       "decode_sparse_attn_hbm_util": ("kernels", "device_trace", "higher"),
       "decode_kept_share": ("serving", "program_counter", "lower")}
# itl_p95_ms and what moves it: the 45 s window holds about five of the
# cell's completions, so its serve_tok_s is the seed's (PERF.md, section 2).
# Not prefill_device_ms_per_chunk: a 5 s slice of this cell may hold no
# prefill chunk, and a listed metric that reads nothing refuses a run.
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
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_local_experts"]
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key
    assert cfg["published"] == {
        key: PUBLISHED[key] for key in entry["reduced"]}


def test_the_cut_and_what_the_file_states_besides():
    cfg = _config()
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"]) == (8, 16)
    assert cfg["held_experts"] == [0, 16]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "num_local_experts": 128}
    assert cfg["deployment"]["this_chip"] == {"stage": 0,
                                              "experts": [0, 16]}
    assert (cfg["deployment"]["expert_parallel"],
            cfg["deployment"]["pipeline_stages"]) == (8, 6)
    assert "batch_against_the_deployment" in cfg["deployment"]
    for key in ("q_norm_k_norm", "indexer_input", "index_key_norm",
                "index_rope_dim", "index_key_dtype", "chunk_sizes", "init",
                "ties", "serve"):
        assert key in cfg["assumed"], key
    assert cfg["serve"] == {"param_dtype": "bfloat16", "num_slots": 16,
                            "max_seq_len": 36864, "block_size": 16,
                            "prefill_chunk": 512}
    assert cfg["family"] == "keye_vl2"


def test_the_family_builds_the_published_widths():
    from benchmark.lib.families import keye_vl2

    cfg = _config()
    m = keye_vl2.build(cfg, max_seq_len=64)
    assert (m.d_model, m.num_heads, m.kv_heads, m.head_dim) == (
        2048, 32, 4, 128)
    assert (m.d_ff, m.num_experts, m.top_k, m.held) == (768, 128, 8,
                                                        (0, 16))
    assert (m.index_heads, m.index_dim, m.index_rope_dim, m.index_topk) \
        == (16, 64, 32, 2048)
    assert (m.vocab_size, m.rope_theta, m.norm_eps) == (151936, 1e7, 1e-6)
    assert m.mixers == ("sparse_attention",) * 8 and m.shared_ff == 0
    with pytest.raises(ValueError, match="held_experts"):
        keye_vl2.build(dict(cfg, num_local_experts=8))
    with pytest.raises(ValueError, match="renormalised"):
        keye_vl2.build(dict(cfg, norm_topk_prob=False))


# ---- bytes ----------------------------------------------------------------------

def test_shape_arithmetic_of_the_sparse_model():
    cfg = _config()
    s = shapes_sparse
    assert s.attn_params(cfg) == 18_874_624               # 18.87M
    assert s.index_params(cfg) == (2048 * 16 * 64 + 2048 * 64 + 2048 * 16
                                   + 128)                   # 2.26M
    assert s.router_params(cfg) == 262_144
    assert s.experts_params(cfg) == 75_497_472              # 16 x 4.72M
    assert s.layer_params(cfg) == 96_899_456                # 96.90M
    assert s.weight_bytes(cfg) == pytest.approx(2.80e9, rel=2e-3)
    assert s.kv_bytes_per_position(cfg) == 2048
    assert s.index_bytes_per_position(cfg) == 128
    # 17.4 KB a position over 8 layers; 16 x 2,304 + 1 blocks of 16
    assert 8 * (2048 + 128) == 17_408
    assert s.pool_bytes(cfg) == 8 * 36_865 * 16 * 2176
    assert s.pool_bytes(cfg) == pytest.approx(10.27e9, rel=1e-3)
    # a decode step at a mean live context of 18.8k on 16 slots
    index_tokens, selected = 16 * 18_800, 16 * 2048
    sparse = s.sparse_attn_step_bytes(cfg, index_tokens, selected)
    assert sparse == 8 * (2 * s.index_params(cfg) + index_tokens * 128
                          + selected * 2048)
    assert 8 * index_tokens * 128 == pytest.approx(0.31e9, rel=0.02)
    assert 8 * selected * 2048 == pytest.approx(0.54e9, rel=0.01)
    # the whole step: every weight but the embedding (one row a slot)
    # once, 1.55 GB of layers and 0.62 GB of head, and the sparse part
    assert (s.weight_bytes(cfg) - 2 * 151_936 * 2048 + sparse) == \
        pytest.approx(3.0e9, rel=0.05)


# ---- the readers ----------------------------------------------------------------

MS = 1_000_000
TALLY = "tpu_ddp.serve.tally"


def _program(with_scopes=True, with_counts=True):
    """Two decode executions of 30 ms: 6 ms under attn/index, 3 under
    attn/sparse, 2 elsewhere in attn, 10 in the expert layer, 5 in the
    head, 4 bare; one prefill of 50 ms. Tallies 10 decode steps apart that
    scored 300,000 live positions and kept 32,768."""
    dev = "/device:TPU:0"
    scopes, programs = [], []
    for t0 in (100 * MS, 200 * MS):
        programs.append(["serve_decode", t0, 30 * MS])
        t = t0
        for path, d in (("jit(serve_decode)/attn/index/dot_general", 4),
                        ("jit(serve_decode)/attn/index/sort", 2),
                        ("jit(serve_decode)/attn/sparse/gather", 3),
                        ("jit(serve_decode)/attn/dot_general", 2),
                        ("jit(serve_decode)/mlp/moe/experts/gather", 10),
                        ("jit(serve_decode)/head/dot_general", 5),
                        ("", 4)):
            if not with_scopes:
                path = path.replace("/index", "").replace("/sparse", "")
            scopes.append([path, t, d * MS])
            t += d * MS
    programs.append(["serve_prefill", 300 * MS, 50 * MS])
    scopes.append(["jit(serve_prefill)/attn/index/dot_general", 300 * MS,
                   50 * MS])

    def tally(start, k):
        counts = {"steps": k, "decode_steps": k, "decode_rows": 16 * k,
                  "context_tokens": 29_984 * k}
        if with_counts:
            counts.update(selected_tokens=3_276.8 * k,
                          index_scored_tokens=30_000 * k)
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
    need = shapes_sparse.sparse_attn_step_bytes(cfg, 30_000, 3_276.8)
    want = {"decode_index_share": 100 * 6 / 30,
            "decode_sparse_attn_share": 100 * 9 / 30,
            "decode_sparse_attn_hbm_util": 100 * need / (819e9 * 9e-3),
            "decode_kept_share": 100 * 3_276.8 / 30_000}
    for name in NEW:
        assert _read(name, rec) == pytest.approx(want[name]), name


def test_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A dense cell's trace (the programs, no indexer scopes, a tally
    without the selection's counts), an empty one, and a program of this
    model without the scopes and the counts."""
    dense = json.loads((ROOT / "benchmark/configs/starcoder2-3b.json")
                       .read_text())
    bare = _program(with_scopes=False, with_counts=False)
    for rec in (_record(bare, dense), _record(bare),
                _record({"spans": [], "programs": {}, "scopes": {}})):
        for name in NEW:
            assert _read(name, rec) is None, name


# ---- the runner on a tiny configuration of the family ----------------------------

TINY_TRAFFIC = {"runner": "serve_model", "clients": 3, "warm_completions": 2,
                "requests": [[40, 6], [55, 9], [70, 12], [45, 7], [60, 5],
                             [50, 10]],
                # within the top 16, where bf16 alone parts the engine
                # from the float32 reference: a selection that bf16 flips
                # at the cutoff of 16 moves a log-probability by up to 0.3
                # at these widths (tests/test_sparse_moe.py holds the
                # selection itself to the reference in float32)
                "check": {"prompt_lens": [12, 9], "new_tokens": 4,
                          "logprob_tol": 0.05}}


def _tiny_config() -> dict:
    cfg = copy.deepcopy(_config())
    cfg.update(name="tiny-keye", hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=32, num_experts=16,
               num_local_experts=8, held_experts=[0, 8],
               num_experts_per_tok=4, vocab_size=512, num_hidden_layers=2,
               index_rope_dim=4,
               sa_config=dict(cfg["sa_config"], indexer_num_heads=4,
                              indexer_head_dim=8, topk=16),
               serve={"param_dtype": "float32", "num_slots": 3,
                      "max_seq_len": 96, "block_size": 4,
                      "prefill_chunk": 16})
    return cfg


def _serve_tiny(tmp_path, monkeypatch, capsys, traffic=TINY_TRAFFIC):
    """The runner on the tiny cell, a window of 1 s: (exit code, the
    detail line, the result line)."""
    import jax

    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic" / "tiny-long.json").write_text(json.dumps(traffic))
    (tmp_path / "configs" / "tiny-keye.json").write_text(
        json.dumps(_tiny_config()))
    spec = json.loads((HERE / "tiny" / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-keye", "source": "test", "reduced": [],
        "file": str(tmp_path / "configs" / "tiny-keye.json"), "why": "test"})
    spec["workloads"].append({"name": "sparse-long", "config": "tiny-keye",
                              "traffic": "tiny-long", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("serve_tok_s", "itl_p95_ms"):
            m["workloads"].append("sparse-long")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "SPEC", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(run, "SEARCH", run.SEARCH + [tmp_path])
    monkeypatch.setattr(run, "check_devices",
                        lambda chips: jax.devices()[:chips])
    rc = run.main(["--workload", "sparse-long", "--seed", str(2 ** 31 + 77),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-2]), json.loads(out[-1])


def test_runner_serves_the_family_end_to_end(tmp_path, monkeypatch, capsys):
    rc, detail, line = _serve_tiny(tmp_path, monkeypatch, capsys)
    assert rc == 0 and line["correct"] is True, detail["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    # float32 weights, bf16 compute
    assert detail["notes"]["logprob_check"]["max_abs_diff"] < 0.05


@pytest.mark.parametrize("fault", ["none", "dense", "fp8"])
def test_the_check_fails_a_planted_fault(fault, tmp_path, monkeypatch,
                                         capsys):
    """The runner's own check (``serve_model._logprob_check`` against the
    traffic's ``logprob_tol``, here the cell's 0.03) on a first check
    prompt of 40 positions, so that the top 16 bites, with the engine
    computing in float32 so that nothing but the fault parts it from the
    reference: dense attention in place of the selection (the engine's
    model with ``index_topk`` = ``max_seq_len``, the reference's as the
    configuration has it), or the engine's weights rounded to float8 e4m3
    (the reference keeping them), makes the run not ``correct``."""
    import dataclasses

    import jax.numpy as jnp

    import tpu_ddp.serve
    from benchmark.lib.families import keye_vl2
    from benchmark.reference import keye_vl2 as reference

    build = keye_vl2.build

    def planted(config, **kw):
        model = build(config, compute_dtype=jnp.float32, **kw)
        if fault == "dense":
            model = dataclasses.replace(model, index_topk=kw["max_seq_len"])
        return model

    class Fp8Engine(tpu_ddp.serve.ServeEngine):
        def __init__(self, model, params, **kw):
            super().__init__(model, reference.rounded(params, "fp8"), **kw)

    monkeypatch.setattr(keye_vl2, "build", planted)
    if fault == "fp8":
        monkeypatch.setattr(tpu_ddp.serve, "ServeEngine", Fp8Engine)
    check = dict(TINY_TRAFFIC["check"], prompt_lens=[40, 9],
                 logprob_tol=0.03)
    rc, detail, line = _serve_tiny(tmp_path, monkeypatch, capsys,
                                   dict(TINY_TRAFFIC, check=check))
    diff = detail["notes"]["logprob_check"]["max_abs_diff"]
    assert line["attempted"] > 0 and line["failed"] == 0
    if fault == "none":
        assert rc == 0 and line["correct"] is True and diff < 1e-3, diff
    else:
        assert line["correct"] is False and diff > 0.03, diff
        assert detail["notes"]["checks"] == {"all_requests_ok": True,
                                             "logprobs_agree": False}


# ---- the cell and the lists it joined ---------------------------------------------

def check_spec(spec):
    """The cell's own entries and the lists it joined, by name and by
    membership, so that a later entry or cell changes nothing here."""
    for name, (layer, source, better) in NEW.items():
        check_entry(spec, name, [CELL], unit="%", better=better,
                    source=source, layer=layer, moves="itl_p95_ms")
    for name in JOINED:
        check_cells(entry(spec, name), [CELL])
    # what moves serve_tok_s does not list the cell, which does not
    # report it
    for m in spec["per_layer"]:
        if m["moves"] == "serve_tok_s":
            assert CELL not in m.get("workloads", [CELL]), m["name"]


def test_the_cell_and_the_lists_it_joined():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "serve-long16", 1)
    check_spec(SPEC)
    for name in NEW:
        assert run.find("layer_metrics", name, ".py").is_file()


def test_the_traffic_is_a_fixed_list_of_long_contexts():
    traffic = json.loads((ROOT / "benchmark/traffic/serve-long16.json")
                         .read_text())
    pairs = traffic["requests"]
    assert traffic["runner"] == "serve_model" and traffic["clients"] == 16
    assert traffic["warm_completions"] >= 16 and len(pairs) == 64
    prompts, outputs = sorted(p for p, _ in pairs), sorted(o for _, o in pairs)
    assert 8192 <= prompts[0] and prompts[-1] <= 32768
    assert 1024 <= outputs[0] and outputs[-1] <= 4096
    assert 15_000 < prompts[32] < 17_500 and 1_900 < outputs[32] < 2_200
    assert max(p + o for p, o in pairs) <= _config()["serve"]["max_seq_len"]
    assert traffic["check"]["prompt_lens"] == [5000, 300]
    assert traffic["check"]["new_tokens"] == 8
