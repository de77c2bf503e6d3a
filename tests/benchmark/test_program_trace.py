"""The readers of the program's own spans, counts, programs and scopes
(``benchmark/lib/program_trace.py`` and the twelve per-layer metrics
built on it): CPU only, on recorded slices of real v5e traces.

``recorded_serve_trace.json.gz``: three engine steps of a traced
``sc2-serve-gen`` run (PR 26's first traced chip run of the change), the
middle one carrying a 231-token prefill chunk. ``recorded_train_trace.
json.gz``: two steps of ``sc2-train-s4k`` from the same call. Each holds
``trace`` (``lib/trace.py``'s dict, names cut to 100 characters),
``program`` (``program_trace``'s dict) and ``window``. The values the
readers gave on them when recorded are in
``recorded_program_trace.expected.json``; some are re-derived by hand
below."""

import gzip
import json
import struct
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
for path in (ROOT, HERE):    # the program; _spec.py beside this file
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

from _spec import check_cells, entry  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.lib import program_trace, shapes, trace  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
EXPECTED = json.loads(
    (HERE / "recorded_program_trace.expected.json").read_text())
SERVE, TRAIN = "sc2-serve-gen", "sc2-train-s4k"
READERS = {
    "engine_idle_ms_schedule": SERVE, "engine_idle_ms_tables": SERVE,
    "engine_idle_ms_dispatch": SERVE, "engine_idle_ms_emit": SERVE,
    "decode_device_ms_per_step": SERVE,
    "prefill_device_ms_per_chunk": SERVE, "decode_kv_move_share": SERVE,
    "decode_program_hbm_util": SERVE, "live_context_tokens_mean": SERVE,
    "prefill_tokens_per_step": SERVE, "flash_mxu_util": TRAIN,
    "optimizer_time_share": TRAIN,
}
EMPTY = {"spans": [], "programs": {}, "scopes": {}}


def _slice(cell: str) -> dict:
    kind = "serve" if cell == SERVE else "train"
    with gzip.open(HERE / f"recorded_{kind}_trace.json.gz", "rt") as f:
        return json.load(f)


def record(cell: str, **over) -> SimpleNamespace:
    """What ``run.py`` hands a reader, from the cell's recorded slice."""
    data = _slice(cell)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    config = next(c for c in SPEC["configs"]
                  if c["name"] == entry["config"])
    fields = dict(
        trace=data["trace"], program=data["program"],
        window=tuple(data["window"]), counters={}, values={}, cell=entry,
        config=run.load_json(ROOT / config["file"]),
        traffic=run.load_json(run.find("traffic", entry["traffic"],
                                       ".json")),
        device={"kind": "TPU v5 lite", "count": 1})
    fields.update(over)
    return SimpleNamespace(**fields)


def read(name: str, rec):
    return run.load_module("layer_metrics", name).read(rec)


# ---- every reader -----------------------------------------------------------

@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_the_recorded_slice(name):
    value = read(name, record(READERS[name]))
    assert value is not None and value > 0
    assert value == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_in_a_trace_without_its_names(name):
    """PR 24's recorded trace is of the program before it named
    anything (the parent the driver lays these files over): no span, the
    kernels called after their Python wrappers, and no program dict."""
    with gzip.open(HERE / "recorded_trace.json.gz", "rt") as f:
        old = json.load(f)
    window = trace.span_window(old, "bench.traced")
    assert read(name, record(READERS[name], trace=old, program=EMPTY,
                             window=window)) is None
    # ... nor where the names are there and this reader's are not
    other = record(SERVE if READERS[name] == TRAIN else TRAIN)
    mine = record(READERS[name])
    assert read(name, record(READERS[name], trace=other.trace,
                             program=other.program,
                             window=other.window)) is None
    assert read(name, mine) is not None


# PR 24's eleven per-layer entries, which came before every reader above
FIRST = ["compile_s", "compiles_in_window", "train_device_idle_share", "mfu",
         "flash_time_share", "slot_occupancy_mean", "host_ms_per_engine_step",
         "decode_hbm_util", "serve_device_idle_share", "train_peak_hbm_gb",
         "serve_peak_hbm_gb"]


def check_benchmark_json(spec, name):
    """The entry by its name and its cell by membership: a later entry or
    cell changes nothing here."""
    m = entry(spec, name)
    assert set(m) == {"name", "unit", "better", "source", "layer",
                      "moves", "workloads"}
    check_cells(m, [READERS[name]])
    check_cells(entry(spec, m["moves"]), [READERS[name]])
    assert m["layer"] in {entry(spec, n)["layer"] for n in FIRST}
    assert m["source"] == ("program_counter" if "tokens" in name
                           else "device_trace")
    assert m["better"] in ("lower", "higher")
    assert m["unit"] == ("%" if name.endswith(("_share", "_util"))
                         else "tokens" if "tokens" in name else "ms")
    # appended, nothing before it touched: PR 24's eleven come first
    names = [e["name"] for e in spec["per_layer"]]
    assert max(map(names.index, FIRST)) < names.index(name)


def check_spec(spec):
    for name in READERS:
        check_benchmark_json(spec, name)


@pytest.mark.parametrize("name", sorted(READERS))
def test_benchmark_json_entry(name):
    check_benchmark_json(SPEC, name)
    assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()


# ---- the values, by hand ----------------------------------------------------

def test_serve_values_by_hand():
    rec = record(SERVE)
    prog = rec.program
    decodes = [e for e in prog["spans"] if e[0] == "tpu_ddp.serve.decode"]
    assert [e[3]["context_tokens"] for e in decodes] == [12347, 12038, 12070]
    assert EXPECTED["live_context_tokens_mean"] == pytest.approx(
        (12347 + 12038 + 12070) / 3)
    assert EXPECTED["prefill_tokens_per_step"] == 231 / 3
    runs = [d for n, _, d in prog["programs"]["/device:TPU:0"]
            if n == "serve_decode"]
    assert EXPECTED["decode_device_ms_per_step"] == pytest.approx(
        sum(runs) / 3 / 1e6)
    # one step's roofline share: bf16 weights + its own live K/V
    need = shapes.decode_step_bytes(rec.config, 12347)
    assert need == pytest.approx(6.06e9 + 12347 * 30720, rel=2e-3)
    by_hand = [shapes.decode_step_bytes(rec.config, c) / (819e9 * d / 1e9)
               for c, d in zip((12347, 12038, 12070), runs)]
    assert EXPECTED["decode_program_hbm_util"] == pytest.approx(
        100 * sum(by_hand) / 3)
    assert 10 < EXPECTED["decode_program_hbm_util"] < 100
    # the four idle shares are the engine step's host time, seen inside
    idle = sum(EXPECTED[f"engine_idle_ms_{k}"]
               for k in ("schedule", "tables", "dispatch", "emit"))
    assert idle == pytest.approx(read("host_ms_per_engine_step", rec),
                                 rel=0.15)


def test_train_values_by_hand():
    rec = record(TRAIN)
    ops = rec.trace["devices"]["/device:TPU:0"]["ops"]
    kernels = {}
    for name, _, dur in ops:
        if trace.is_kernel(name):
            stem = trace.short_name(name).split(":")[0]
            kernels[stem] = kernels.get(stem, 0) + dur
    # each kernel is found by the name the program gave it
    assert set(kernels) == {"flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"}
    cfg = rec.config
    flops = (3 * 4 * cfg["hidden_size"] * 4096 * cfg["num_hidden_layers"]
             / 2 * 4096)
    assert EXPECTED["flash_mxu_util"] == pytest.approx(
        100 * flops / (197e12 * sum(kernels.values()) / 2 / 1e9), rel=1e-6)
    assert EXPECTED["flash_mxu_util"] < 100
    assert 0 < EXPECTED["optimizer_time_share"] < 100


def test_programs_of_the_slices_carry_names_of_their_own():
    serve = {n for n, _, _ in record(SERVE).program["programs"]
             ["/device:TPU:0"]}
    train = {n for n, _, _ in record(TRAIN).program["programs"]
             ["/device:TPU:0"]}
    assert {"serve_decode", "serve_prefill"} <= serve
    assert "lm_train_step" in train and "step" not in serve | train
    paths = {p for p, _, _ in record(SERVE).program["scopes"]
             ["/device:TPU:0"]}
    assert any(p.startswith("jit(serve_decode)/attn/kv_gather/")
               for p in paths)
    assert any(p.startswith("jit(serve_prefill)/attn/kv_write/")
               for p in paths)


# ---- the pieces -------------------------------------------------------------

TOY = [["tpu_ddp.serve.step", 0, 100, {}],
       ["tpu_ddp.serve.schedule", 5, 10, {}],
       ["tpu_ddp.serve.admit", 8, 2, {}],
       ["tpu_ddp.serve.decode", 20, 70, {}],
       ["tpu_ddp.serve.decode.tables", 20, 10, {}],
       ["tpu_ddp.serve.decode.fetch", 40, 50, {}]]


def test_self_time_on_a_toy_trace():
    assert program_trace.self_time(TOY) == {
        "tpu_ddp.serve.step": 5 + 5 + 10, "tpu_ddp.serve.schedule": 8,
        "tpu_ddp.serve.admit": 2, "tpu_ddp.serve.decode": 10,
        "tpu_ddp.serve.decode.tables": 10,
        "tpu_ddp.serve.decode.fetch": 50}
    assert sum(program_trace.self_time(TOY).values()) == 100


def test_idle_by_span_on_a_toy_trace():
    ops = [["%a = f32[1] add()", 45, 40]]          # busy 45..85
    rec = SimpleNamespace(
        trace={"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
               "host": []}, window=(0, 100))
    idle = program_trace.idle_by_span(rec, TOY)
    assert idle == {"tpu_ddp.serve.step": 20, "tpu_ddp.serve.schedule": 8,
                    "tpu_ddp.serve.admit": 2, "tpu_ddp.serve.decode": 10,
                    "tpu_ddp.serve.decode.tables": 10,
                    "tpu_ddp.serve.decode.fetch": 5 + 5}
    assert sum(idle.values()) == 100 - 40
    rec.program = {"spans": TOY, "programs": {}, "scopes": {}}
    assert program_trace.engine_idle_ms(
        rec, ("tpu_ddp.serve.schedule", "tpu_ddp.serve.admit")) == 10 / 1e6
    assert program_trace.engine_idle_ms(rec, ("tpu_ddp.nothing",)) is None


def test_names_and_scope_paths():
    assert program_trace.program_name(
        "jit_serve_decode(17007165299273803297)") == "serve_decode"
    assert program_trace.program_name("jit_lm_train_step") == "lm_train_step"
    assert program_trace.scope_parts(
        "jit(serve_decode)/attn/kv_gather/gather") == ["attn", "kv_gather"]
    assert program_trace.scope_parts(
        "jit(lm_train_step)/transpose(jvp(attn))/jit(_bwd_impl)/"
        "flash_bwd_dq/pallas_call") == ["attn", "_bwd_impl", "flash_bwd_dq"]
    assert program_trace.scope_parts("") == []
    assert program_trace.scope_parts("jit(f)/add") == []


def test_trace_dir_is_where_run_py_writes():
    assert program_trace.TRACE_DIR == run.TRACE_DIR


def test_no_trace_loads_empty(tmp_path):
    assert program_trace.load(tmp_path) == EMPTY


# ---- the file's bytes -------------------------------------------------------

def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def test_event_metadata_from_hand_made_bytes(tmp_path):
    """An XSpace with one device plane: two stat names, three events; one
    carries tf_op as a string, one as a reference, one not at all. The
    plane's ``lines`` (field 3) and a fixed64 are skipped."""
    def entry(key, message):
        return _field(1, key) + _field(2, message)

    stat_meta = [entry(1, _field(1, 1) + _field(2, b"tf_op")),
                 entry(2, _field(1, 2) + _field(2, b"flops")),
                 entry(3, _field(1, 3) + _field(2, b"jit(f)/mlp/dot"))]
    events = [
        entry(1, _field(1, 1) + _field(2, b"%fusion.1 = ...")
              + _field(5, _field(1, 2) + _field(3, 99))
              + _field(5, _field(1, 1) + _field(5, b"jit(f)/attn/add"))),
        entry(2, _field(1, 2) + _field(2, b"%dot.2") + _field(4, b"dot.2")
              + _field(5, _field(1, 1) + _field(7, 3))),
        entry(3, _field(1, 3) + _field(2, b"%copy.3")
              + _field(5, _field(1, 2) + _field(3, 5))),
    ]
    plane = (_field(1, 7) + _field(2, b"/device:TPU:0")
             + _field(3, b"\x08\x01 not parsed")
             + _varint(9 << 3 | 1) + struct.pack("<d", 1.5)
             + b"".join(_field(4, e) for e in events)
             + b"".join(_field(5, s) for s in stat_meta))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, _field(2, b"/host:CPU")))
    assert program_trace.event_metadata(str(path)) == {
        "/device:TPU:0": {"%fusion.1 = ...": "jit(f)/attn/add",
                          "%dot.2": "jit(f)/mlp/dot",
                          "dot.2": "jit(f)/mlp/dot"}}


def test_load_reads_spans_and_counts_from_a_real_file(tmp_path):
    """A CPU profiler session: no TPU plane, so no programs and no
    scopes, but the spans and their counts come back."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("tpu_ddp.serve.decode", slots=3,
                                      context_tokens=17):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            jnp.arange(4.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    prog = program_trace.load(tmp_path)
    assert [(e[0], e[3]) for e in prog["spans"]] == [
        ("tpu_ddp.serve.decode", {"slots": 3, "context_tokens": 17})]
    assert prog["programs"] == {} and prog["scopes"] == {}
    assert program_trace.load(tmp_path) is prog        # once per file
    rec = SimpleNamespace(window=(0, 2 ** 62), trace={"devices": {}},
                          device={"kind": "cpu", "count": 1})
    assert program_trace.of(rec) is not None
