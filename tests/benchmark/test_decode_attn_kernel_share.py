"""``decode_attn_kernel_share``: the paged decode kernel's share of the
device time of ``serve_decode``, on a synthetic slice (two executions of
the program with the kernel's events inside, one prefill execution with
an event that must not count) and on the recorded slice of the program
before it had the kernel, where the reader finds nothing."""

import gzip
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

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "decode_attn_kernel_share"
DEV = "/device:TPU:0"
CALL = ('%{}.{} = bf16[32,2,12,128]{{3,2,1,0:T(8,128)(2,1)}} custom-call('
        ' custom_call_target="tpu_custom_call"')
FUSION = "%fusion.{} = bf16[32,3072]{{1,0:T(8,128)(2,1)}} fusion("


def _record(ops, programs, window=(0, 10_000)):
    return SimpleNamespace(
        trace={"devices": {DEV: {"ops": ops, "async": []}}, "host": []},
        program={"spans": [], "programs": {DEV: programs}, "scopes": {}},
        window=window)


def _read(rec):
    return run.load_module("layer_metrics", NAME).read(rec)


PROGRAMS = [["serve_decode", 1000, 2000], ["serve_prefill", 3500, 1000],
            ["serve_decode", 5000, 2000]]
OPS = [[FUSION.format(1), 1000, 300],
       [CALL.format("paged_decode_attn", 3), 1300, 400],
       [CALL.format("paged_decode_attn", 4), 2000, 200],
       [CALL.format("paged_decode_attn", 9), 3600, 500],   # in prefill
       [CALL.format("quant_matmul", 2), 5000, 700],        # another kernel
       [CALL.format("paged_decode_attn", 3), 5800, 1000]]


def test_share_is_kernel_time_inside_the_program_over_the_programs_time():
    assert _read(_record(OPS, PROGRAMS)) == pytest.approx(
        100.0 * (400 + 200 + 1000) / (2000 + 2000))


def test_executions_not_whole_inside_the_window_do_not_count():
    assert _read(_record(OPS, PROGRAMS, window=(0, 6000))) \
        == pytest.approx(100.0 * 600 / 2000)


@pytest.mark.parametrize("ops,programs", [
    ([o for o in OPS if "paged_decode_attn" not in o[0]], PROGRAMS),
    (OPS, [p for p in PROGRAMS if p[0] != "serve_decode"]),
    ([], []),
], ids=["no_kernel", "no_decode_program", "empty"])
def test_absent_kernel_or_program_reads_none(ops, programs):
    assert _read(_record(ops, programs)) is None


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_recorded_slices_of_the_gathering_program_read_none(kind):
    """PR 26's recorded slices: ``serve_decode`` is there and gathers
    (the parent of this metric's PR); the train slice has kernels of
    other names."""
    with gzip.open(HERE / f"recorded_{kind}_trace.json.gz", "rt") as f:
        data = json.load(f)
    assert _read(SimpleNamespace(trace=data["trace"],
                                 program=data["program"],
                                 window=tuple(data["window"]))) is None


def test_benchmark_json_entry():
    entry = SPEC["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "itl_p95_ms", "workloads": ["sc2-serve-gen"]}
    assert (ROOT / "benchmark" / "layer_metrics" / f"{NAME}.py").is_file()
