"""The benchmark's own tests: CPU only, tiny configurations (under
``tiny/``, in no cell), each runner end to end through ``run.main()`` in
this process with the device check stubbed (the command line has no
switch that lets a CPU through)."""

import gzip
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
TINY = HERE / "tiny"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import run  # noqa: E402
from benchmark.lib import loadgen, shapes, trace  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture
def tiny(monkeypatch):
    """run.py pointed at the tiny cells, any device admitted."""
    import jax

    monkeypatch.setattr(run, "SPEC", TINY / "BENCHMARK.json")
    monkeypatch.setattr(run, "SEARCH", run.SEARCH + [TINY])
    monkeypatch.setattr(run, "check_devices",
                        lambda chips: jax.devices()[:chips])
    return run


def recorded_trace() -> dict:
    """A slice of a real v5e trace: the last two steps of this PR's first
    traced chip run of ``sc2-train-s4k``, names cut to 100 characters."""
    with gzip.open(HERE / "recorded_trace.json.gz", "rt") as f:
        return json.load(f)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ---- each runner end to end ------------------------------------------------

@pytest.mark.parametrize("cell,e2e", [
    ("tiny-lm-train", {"train_throughput"}),
    ("tiny-serve-closed", {"serve_tok_s", "itl_p95_ms"}),
    ("tiny-serve-open", {"itl_p95_ms", "ttft_p50_ms"}),
    ("tiny-ladder", {"train_throughput"}),
])
def test_runner_end_to_end(tiny, capsys, cell, e2e):
    rc = tiny.main(["--workload", cell, "--seed", str(2 ** 31 + 17),
                    "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    # (five steps of VGG-11 at batch 16 do not lower the loss reliably:
    # the ladder's other checks are looked at one by one)
    checks = json.loads(out[-2])["notes"]["checks"]
    assert line["correct"] is True or cell == "tiny-ladder", checks
    assert all(v for k, v in checks.items() if k != "loss_fell"), checks
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == e2e | {"setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_traced_run_reports_per_layer_metrics(tiny, capsys, monkeypatch):
    """--trace 1 on the recorded trace: the line carries the cell's
    per-layer metrics, busy_s / window_s and the breakdown."""
    import jax

    recorded = recorded_trace()
    monkeypatch.setitem(shapes.PEAKS, "cpu", (1.0, 1.0))
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace, "load_xplane", lambda d: recorded)
    rc = tiny.main(["--workload", "tiny-lm-train", "--seed", "5",
                    "--seconds", "1", "--trace", "1"])
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {
        "compile_s", "compiles_in_window", "train_device_idle_share", "mfu",
        "flash_time_share", "train_peak_hbm_gb"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert 1 <= len(line["breakdown"]["idle_gaps"]) <= 10


def test_no_tpu_no_result(monkeypatch, capsys):
    """On this machine JAX finds a CPU: exit 2, nothing on stdout."""
    rc = run.main(["--workload", SPEC["workloads"][0]["name"], "--seed",
                   "1", "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_too_few_chips_no_result(monkeypatch, capsys):
    import jax

    one_tpu = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    monkeypatch.setattr(jax, "devices", lambda: one_tpu)
    with pytest.raises(run.BenchError, match="asks for 4 chips"):
        run.check_devices(4)
    assert run.check_devices(1) == one_tpu


# ---- the harness takes later cells as data ---------------------------------

def test_new_cell_config_runner_and_metric_are_dropped_in(
        tiny, tmp_path, monkeypatch, capsys):
    """A later PR's cell: new files and new entries only."""
    (tmp_path / "runners").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "runners" / "sleeper.py").write_text(
        "import time\n"
        "def run(bench):\n"
        "    bench.open_window()\n"
        "    n = 0\n"
        "    while bench.elapsed() < bench.seconds:\n"
        "        bench.tick(); time.sleep(bench.traffic['nap_s']); n += 1\n"
        "    bench.close_window()\n"
        "    return {'correct': True, 'attempted': n, 'failed': 0,\n"
        "            'values': {'naps_per_s': n / bench.seconds},\n"
        "            'counters': {'width': bench.config['width']}}\n")
    (tmp_path / "traffic" / "naps.json").write_text(
        '{"runner": "sleeper", "nap_s": 0.01}')
    (tmp_path / "configs" / "bed.json").write_text('{"width": 7}')
    (tmp_path / "layer_metrics" / "bed_width.py").write_text(
        "def read(record):\n    return record.counters['width']\n")
    (tmp_path / "layer_metrics" / "nothing_to_read.py").write_text(
        "def read(record):\n    return None\n")
    spec = json.loads((TINY / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "bed", "source": "test", "reduced": [],
                            "file": str(tmp_path / "configs" / "bed.json"),
                            "why": "test"})
    spec["workloads"].append({"name": "bed.naps", "config": "bed",
                              "traffic": "naps", "chips": 1, "why": "t"})
    spec["end_to_end"].append({"name": "naps_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["bed.naps"]})
    for name in ("bed_width", "nothing_to_read"):
        spec["per_layer"].append({"name": name, "unit": "cm",
                                  "better": "higher", "layer": "bed",
                                  "source": "program_counter",
                                  "moves": "naps_per_s",
                                  "workloads": ["bed.naps"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "SPEC", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(run, "SEARCH", run.SEARCH + [tmp_path])
    args = ["--workload", "bed.naps", "--seed", "1", "--seconds", "0.2"]
    assert run.main(args + ["--trace", "0"]) == 0
    line = last_line(capsys)
    assert set(line["metrics"]) == {"naps_per_s", "setup_s"}

    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace, "load_xplane", lambda d: recorded_trace())
    assert run.main(args + ["--trace", "1"]) == 0
    line = last_line(capsys)
    # compile_s and compiles_in_window are every cell's; the reader that
    # found nothing is left out.
    assert set(line["metrics"]) == {"compile_s", "compiles_in_window",
                                    "bed_width"}
    assert line["metrics"]["bed_width"] == {"value": 7.0, "unit": "cm"}


# ---- BENCHMARK.json --------------------------------------------------------

def test_benchmark_json_names_units_and_references():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in SPEC["workloads"]]
    configs = {c["name"]: c for c in SPEC["configs"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = cells + list(configs) + [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for n in names + [w["traffic"] for w in SPEC["workloads"]] \
            + [k for c in SPEC["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= 1
    for w in SPEC["workloads"]:
        assert w["config"] in configs and len(w["why"]) <= 200
        assert run.find("traffic", w["traffic"], ".json").is_file()
        assert len(run.metrics_of(SPEC, "end_to_end", w["name"])) >= 2
        assert run.metrics_of(SPEC, "per_layer", w["name"])
    for c in SPEC["configs"]:
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
        assert (ROOT / c["file"]).is_file()
        assert sorted(json.loads((ROOT / c["file"]).read_text())["reduced"]) \
            == sorted(c["reduced"])
    for m in SPEC["per_layer"]:
        # the metric it should move is reported wherever it is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]
        assert run.find("layer_metrics", m["name"], ".py").is_file()


# ---- load generation -------------------------------------------------------

@pytest.mark.parametrize("traffic", ["serve-gen", "serve-code"])
def test_request_list_is_the_same_multiset_for_two_seeds(traffic):
    pairs = json.loads((ROOT / "benchmark" / "traffic"
                        / f"{traffic}.json").read_text())["requests"]
    runs = []
    for seed in (1, 2 ** 31 + 5):
        it = loadgen.order_requests(pairs, np.random.default_rng([seed, 0]))
        runs.append([next(it) for _ in range(2 * len(pairs))])
    assert runs[0] != runs[1]                       # the seed orders it
    for r in runs:                                  # and only orders it
        assert sorted(r[:len(pairs)]) == sorted(map(tuple, pairs))
        assert sorted(r[len(pairs):]) == sorted(map(tuple, pairs))
    assert all(p + o <= 4096 for p, o in pairs)


class _FakeEngine:
    """Answers every request with two tokens, one per step, and stalls
    (a step that takes ``stall`` seconds of the fake clock) on demand."""

    def __init__(self):
        self.now = 0.0
        self.queue: list = []
        self.stall_at_step, self.stall = None, 0.0
        self.steps = 0

    def clock(self):
        return self.now

    def submit(self, prompt_len, output_len):
        h = SimpleNamespace(token_times=[], done=False, max_new_tokens=2)
        self.queue.append(h)
        return h

    def step(self):
        self.steps += 1
        self.now += self.stall if self.steps == self.stall_at_step else 0.01
        for h in self.queue:
            h.token_times.append(self.now)
            h.done = len(h.token_times) == 2
        self.queue = [h for h in self.queue if not h.done]
        return True


def test_ttft_runs_from_when_the_request_was_due():
    """A stalled engine: requests due during the stall are submitted
    late, and the wait counts."""
    eng = _FakeEngine()
    eng.stall_at_step, eng.stall = 3, 1.0
    load = loadgen.Load(eng.submit, eng.step, iter(lambda: (4, 2), None),
                        arrivals=iter([0.005, 0.025, 0.5, 5.0]),
                        clock=eng.clock)
    load.run(lambda l: eng.now > 1.2)
    stats = loadgen.window_stats(load.sent, 0.0, 2.0)
    assert stats["attempted"] == 3
    due = [round(d, 3) for d, _, _ in load.sent]
    assert due == [0.005, 0.025, 0.5]
    # the third was due at 0.5, inside the stall that ended at 1.02; it
    # was submitted then and got its token a step later
    assert stats["lateness_ms"][2] == pytest.approx(520.0)
    assert stats["ttft_ms"][2] == pytest.approx(530.0)
    # the first was due at 0.005, seen after the step that ended at 0.01,
    # and answered by the next
    assert stats["ttft_ms"][0] == pytest.approx(15.0)


def test_closed_loop_keeps_every_client_busy():
    eng = _FakeEngine()
    load = loadgen.Load(eng.submit, eng.step, iter(lambda: (4, 2), None),
                        clients=3, clock=eng.clock)
    load.run(lambda l: eng.steps >= 10)
    assert len(load.sent) == 3 * 5 and load.completed() == 15
    stats = loadgen.window_stats(load.sent, 0.005, 0.095)
    # three tokens a step, and the nine steps that ended in the window
    assert stats["tokens"] == 27 and stats["tok_s"] == pytest.approx(300.0)
    assert all(g == pytest.approx(10.0) for g in stats["itl_ms"])
    # a later request is due when the last one's answer was complete
    assert load.sent[3][0] == pytest.approx(0.02)


# ---- throughput arithmetic and the yardstick -------------------------------

def test_whole_epoch_throughput(tiny, capsys):
    """The ladder's rate is whole epochs over the time inside them."""
    tiny.main(["--workload", "tiny-ladder", "--seed", "3", "--seconds",
               "0.5", "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    detail, line = json.loads(out[-2]), json.loads(out[-1])
    c = detail["counters"]
    assert c["epochs"] >= 1 and c["steps"] == c["epochs"] * 5  # 72 / 16
    assert c["data_batches"] == c["steps"]
    assert line["metrics"]["train_throughput"]["value"] == pytest.approx(
        c["epochs"] * 72 / c["train_s"])
    assert detail["window_s"] >= c["train_s"]


def test_shape_arithmetic():
    vgg = json.loads((ROOT / "benchmark/configs/vgg11-cifar10.json")
                     .read_text())
    # VGG-11 on 32x32: 153 MFLOP of multiply-adds forward, x2, x3.
    assert shapes.vgg_train_flops_per_image(vgg["plan"]) == pytest.approx(
        3 * 2 * 152.9e6, rel=0.01)
    sc2 = json.loads((ROOT / "benchmark/configs/starcoder2-3b.json")
                     .read_text())
    # 30 layers of 95.9M and a 151M head: 3.03B parameters in matmuls
    assert shapes.lm_matmul_params(sc2) == 30 * 95_944_704 + 150_994_944
    assert shapes.lm_weight_bytes(sc2) == 2 * shapes.lm_matmul_params(sc2)
    assert shapes.kv_bytes_per_token(sc2) == 2 * 30 * 2 * 128 * 2
    assert shapes.peak("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(KeyError):
        shapes.peak("cpu")


def test_reference_agrees_with_the_program_in_float32():
    """The plain reference and TransformerLM on the same weights."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.lm import build_model
    from benchmark.reference import gpt

    cfg = json.loads((TINY / "configs" / "tiny-lm.json").read_text())
    model = build_model(cfg, max_seq_len=32, compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (32,), 0, 256)
    want = jax.nn.log_softmax(model.apply(params, tokens[None])[0], -1)
    got = gpt.log_probs(params, tokens)
    # float32 on both sides: only the order of sums differs
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- the trace reduction ---------------------------------------------------

def _toy_trace():
    """Two devices, 100 ns. Device 0 computes 10-30 and 60-70, with an
    all-reduce in flight 25-50 (so 30-50 is exposed); device 1 computes
    10-40."""
    ar = ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
          "replica_groups={}")
    return {
        "devices": {
            "/device:TPU:0": {
                "ops": [["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 20],
                        ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 60, 10]],
                "async": [[ar, 25, 25]]},
            "/device:TPU:1": {
                "ops": [["%convolution.7 = bf16[4,4]{1,0} convolution(%a, %b)",
                         10, 30]],
                "async": []}},
        "host": [["bench.traced", 0, 100], ["bench.train_epoch", 5, 90],
                 ["bench.data_next", 40, 15]],
    }


def test_reduction_on_a_toy_trace():
    t = _toy_trace()
    assert trace.busy_seconds(t, 0, 100) == pytest.approx(30e-9)
    assert trace.idle_share(t, 0, 100) == pytest.approx(70.0)
    assert trace.idle_share(t, 10, 40) == pytest.approx(
        100 * (1 - 25 / 30))
    assert trace.collective_exposed_seconds(t, 0, 100) == pytest.approx(
        (20 + 0) / 2 * 1e-9)
    assert trace.span_window(t, "bench.traced") == (0, 100)
    # no device ran anything in 0-10, 40-60 and 70-100
    gaps = dict(trace.idle_gaps(t, 0, 100))
    assert gaps == {"bench.train_epoch": pytest.approx(35e-9),
                    "bench.data_next": pytest.approx(15e-9),
                    "bench.traced": pytest.approx(10e-9)}
    ops = trace.top_ops(t, 0, 100)
    assert ops[0] == ["fusion:f32[8] x2", pytest.approx(30e-9)]
    assert ops[1] == ["convolution:bf16[4,4] x1", pytest.approx(30e-9)]
    assert trace.share_of_busy(t, 0, 100, lambda n: "convolution" in n) \
        == pytest.approx(50.0)


def _record(**counters):
    sc2 = json.loads((ROOT / "benchmark/configs/starcoder2-3b.json")
                     .read_text())
    t = _toy_trace()
    t["host"] += [["bench.engine_step", 10, 30], ["bench.engine_step", 50, 30]]
    return SimpleNamespace(trace=t, window=(0, 100), counters=counters,
                           values={}, cell={}, config=sc2, traffic={},
                           device={"kind": "TPU v5 lite", "count": 1})


@pytest.mark.parametrize("metric,counters,want", [
    # two steps, 60 ns of host clock; in 10-80 each device is busy 30 ns
    ("host_ms_per_engine_step", {}, (60 - 30) / 2 / 1e6),
    ("collective_exposed_share", {}, 10.0),
    ("train_device_idle_share", {}, 70.0),
    ("data_wait_ms_per_step", {"data_wait_s": 3.0, "data_batches": 150}, 20.0),
    ("data_wait_ms_per_step", {}, None),
    # 6.06 GB of weights + 32 slots x 1000 tokens x 30.7 kB over 819 GB/s
    # x 80 ms
    ("decode_hbm_util", {"itl_p50_ms": 80.0, "slot_occupancy_mean": 32.0,
                         "mean_context_tokens": 1000.0},
     100 * (2 * 3_029_336_064 + 32_000 * 30_720) / (819e9 * 0.08)),
    ("decode_hbm_util", {"itl_p50_ms": None}, None),
    # 2 x 3.03e9 + 4 x 3072 x 4096 x 30 operations a token, x 3
    ("mfu", {"items_per_s": 10_000.0, "seq_len": 4096},
     100 * 1e4 * 3 * (2 * 3_029_336_064 + 4 * 3072 * 4096 * 30) / 197e12),
    ("compiles_in_window", {"compiles_in_window": 0}, 0),
])
def test_per_layer_readers(metric, counters, want):
    got = run.load_module("layer_metrics", metric).read(_record(**counters))
    assert got == (want if want is None else pytest.approx(want))


def test_interval_arithmetic():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert trace.innermost([["a", 0, 10], ["b", 2, 3], ["c", 3, 1]]) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 10, "a")]


def test_event_names():
    fusion = ("%fusion.11 = (f32[3072,49152]{1,0:T(8,128)}, f32[3072]{0}) "
              "fusion(f32[3072]{0:T(1024)S(1)} %p), kind=kLoop")
    flash = ('%jvp_jit__fwd_impl__.6 = (bf16[24,4096,128]{2,1,0:T(8,128)'
             '(2,1)}, f32[24,1,4096]{2,1,0}) custom-call(bf16[2] %a), '
             'custom_call_target="tpu_custom_call"')
    assert trace.short_name(fusion) == "fusion:f32[3072,49152]"
    assert trace.opcode(fusion) == "fusion"
    assert trace.opcode(flash) == "custom-call" and trace.is_kernel(flash)
    assert not trace.is_kernel(fusion) and not trace.is_collective(fusion)
    assert trace.is_collective("%all-reduce.3 = f32[4]{0} all-reduce(%x)")
    assert trace.is_collective(
        "%ag-done = f32[4]{0} all-gather-done(%all-gather-start.2)")
    assert trace.is_collective(     # wrapped in a generic async pair
        "%all-reduce-start.1 = ((f32[4]{0}), f32[4]{0}) async-start(%x), "
        "calls=%async_computation.2")
    # an operand that is a collective does not make its consumer one
    assert not trace.is_collective(
        "%fusion.9 = f32[4]{0} fusion(f32[4]{0} %all-reduce.3), kind=kLoop")


def test_reduction_on_the_recorded_trace():
    t = recorded_trace()
    lo, hi = trace.span_window(t, "bench.traced")
    busy, idle = trace.busy_seconds(t, lo, hi), trace.idle_share(t, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    assert idle == pytest.approx(100 * (1 - busy * 1e9 / (hi - lo)))
    expected = json.loads((HERE / "recorded_trace.expected.json").read_text())
    assert busy == pytest.approx(expected["busy_s"])
    assert trace.share_of_busy(t, lo, hi, trace.is_kernel) == pytest.approx(
        expected["flash_time_share"])
    assert trace.collective_exposed_seconds(t, lo, hi) == 0.0   # one chip
    gaps = trace.idle_gaps(t, lo, hi)
    assert sum(s for _, s in gaps) == pytest.approx((hi - lo) / 1e9 - busy)
    assert {name for name, _ in gaps} <= {
        "bench.traced", "bench.put_batch", "bench.train_step",
        "bench.read_loss", "(no span)"}
    # the cross-check satellite 2 asks for: idle time per step cannot
    # exceed the time per step
    steps = [e for e in t["host"] if e[0] == "bench.train_step"]
    per_step_ms = (hi - lo) / 1e6 / len(steps)
    assert sum(s for _, s in gaps) * 1e3 / len(steps) < per_step_ms
