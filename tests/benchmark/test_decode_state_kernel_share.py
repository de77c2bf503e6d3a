"""``decode_state_kernel_share``: the state kernel's share of the device
time of ``serve_decode``'s operations, on a synthetic slice (two
executions of the program with the kernel's operations inside under the
path the compiled program gives them, one prefill execution whose
operations must not count) and on the recorded slices of programs that
have no such kernel, where the reader finds nothing."""

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
NAME = "decode_state_kernel_share"
DEV = "/device:TPU:0"
KERNEL = ("jit(serve_decode)/ssm/state/jit(_impl)/ssm_state_step/"
          "pallas_call")
PROGRAMS = [["serve_decode", 1000, 2000], ["serve_prefill", 3500, 1000],
            ["serve_decode", 5000, 2000]]
# [op_name path, start, duration]
SCOPES = [["jit(serve_decode)/ssm/dot_general", 1000, 300],
          [KERNEL, 1300, 400],
          ["jit(serve_decode)/ssm/state/scatter", 1700, 100],
          [KERNEL, 2000, 200],
          ["ragged-dot-none", 2200, 500],
          ["jit(serve_prefill)/ssm/state/dynamic_update_slice", 3600, 500],
          ["jit(serve_decode)/attn/jit(_paged_impl)/paged_decode_attn/"
           "pallas_call", 5000, 700],
          [KERNEL, 5800, 1000],
          ["", 6800, 100]]


def _record(scopes, programs, window=(0, 10_000)):
    return SimpleNamespace(
        trace={"devices": {}, "host": []}, window=window, counters={},
        program={"spans": [], "programs": {DEV: programs},
                 "scopes": {DEV: scopes}})


def _read(rec):
    return run.load_module("layer_metrics", NAME).read(rec)


def test_share_is_the_kernels_time_over_the_programs_operations():
    decode_ops = 300 + 400 + 100 + 200 + 500 + 700 + 1000 + 100
    assert _read(_record(SCOPES, PROGRAMS)) == pytest.approx(
        100.0 * (400 + 200 + 1000) / decode_ops)


def test_executions_not_whole_inside_the_window_do_not_count():
    assert _read(_record(SCOPES, PROGRAMS, window=(0, 6000))) \
        == pytest.approx(100.0 * 600 / 1500)


@pytest.mark.parametrize("scopes,programs", [
    ([s for s in SCOPES if s[0] != KERNEL], PROGRAMS),
    (SCOPES, [p for p in PROGRAMS if p[0] != "serve_decode"]),
    ([[s[0].replace("serve_decode", "serve_prefill"), *s[1:]]
      for s in SCOPES], [["serve_prefill", 0, 9000]]),
    ([], []),
], ids=["plain_body", "no_decode_program", "kernel_in_another_program",
        "empty"])
def test_absent_kernel_or_program_reads_none(scopes, programs):
    assert _read(_record(scopes, programs)) is None


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_recorded_slices_without_the_kernel_read_none(kind):
    """PR 26's recorded slices: a dense model's ``serve_decode`` (no
    state layer), and a train step with kernels of other names."""
    with gzip.open(HERE / f"recorded_{kind}_trace.json.gz", "rt") as f:
        data = json.load(f)
    assert _read(SimpleNamespace(trace=data["trace"],
                                 program=data["program"], counters={},
                                 window=tuple(data["window"]))) is None


def test_benchmark_json_entry():
    entry = next(m for m in SPEC["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "itl_p95_ms",
                     "workloads": ["gr4h-serve-chat"]}
    assert (ROOT / "benchmark" / "layer_metrics" / f"{NAME}.py").is_file()
