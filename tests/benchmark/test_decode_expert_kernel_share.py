"""``decode_expert_kernel_share`` and ``prefill_expert_kernel_share``:
the grouped-matmul kernel's share of the device time of a serve
program's operations, on a synthetic slice (two executions of
``serve_decode`` and one of ``serve_prefill``, each with the kernel's
operations inside under the path the compiled program gives them) and
on slices of programs that have no such kernel (the parent's
``ragged-dot-none`` custom calls; PR 26's recorded slices), where the
readers find nothing. The entries are found by name, never by position.
"""

import gzip
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

from _spec import check_entry  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.lib import scope_time  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
DECODE, PREFILL = "decode_expert_kernel_share", "prefill_expert_kernel_share"
PROGRAM = {DECODE: "serve_decode", PREFILL: "serve_prefill"}
DEV = "/device:TPU:0"


def _kernel(program):
    return (f"jit({program})/mlp/moe/experts/jit(_impl)/grouped_matmul/"
            "pallas_call")


PROGRAMS = [["serve_decode", 1000, 2000], ["serve_prefill", 3500, 1000],
            ["serve_decode", 5000, 2000]]
# [op_name path, start, duration]
SCOPES = [["jit(serve_decode)/mlp/moe/route/sort", 1000, 300],
          [_kernel("serve_decode"), 1300, 400],
          ["jit(serve_decode)/mlp/moe/experts/gather", 1700, 100],
          [_kernel("serve_decode"), 2000, 200],
          ["jit(serve_decode)/ssm/state/jit(_impl)/ssm_state_step/"
           "pallas_call", 2200, 500],
          ["jit(serve_prefill)/mlp/moe/experts/gather", 3500, 100],
          [_kernel("serve_prefill"), 3600, 500],
          ["jit(serve_prefill)/mlp/shared_mlp/dot_general", 4100, 200],
          [_kernel("serve_decode"), 5000, 700],
          ["jit(serve_decode)/mlp/shared_mlp/dot_general", 5800, 1000],
          ["", 6800, 100]]
DECODE_OPS = 300 + 400 + 100 + 200 + 500 + 700 + 1000 + 100
PLAIN = [[("ragged-dot-none" if "grouped_matmul" in s[0] else s[0]), *s[1:]]
         for s in SCOPES]


def _record(scopes, programs, window=(0, 10_000)):
    return SimpleNamespace(
        trace={"devices": {}, "host": []}, window=window, counters={},
        program={"spans": [], "programs": {DEV: programs},
                 "scopes": {DEV: scopes}})


def _read(name, rec):
    return run.load_module("layer_metrics", name).read(rec)


def test_each_share_is_its_programs_kernel_time_over_its_operations():
    rec = _record(SCOPES, PROGRAMS)
    assert _read(DECODE, rec) == pytest.approx(
        100.0 * (400 + 200 + 700) / DECODE_OPS)
    assert _read(PREFILL, rec) == pytest.approx(100.0 * 500 / 800)


def test_executions_not_whole_inside_the_window_do_not_count():
    rec = _record(SCOPES, PROGRAMS, window=(0, 6000))
    assert _read(DECODE, rec) == pytest.approx(100.0 * 600 / 1500)
    assert _read(PREFILL, rec) == pytest.approx(100.0 * 500 / 800)


@pytest.mark.parametrize("name", [DECODE, PREFILL])
@pytest.mark.parametrize("scopes,programs", [
    (PLAIN, PROGRAMS),
    ([s for s in SCOPES if "grouped_matmul" not in s[0]], PROGRAMS),
    (SCOPES, [["serve_spec", 0, 9000]]),
    ([], []),
], ids=["ragged_dot_custom_calls", "no_grouped_product",
        "another_program", "empty"])
def test_absent_kernel_or_program_reads_none(name, scopes, programs):
    assert _read(name, _record(scopes, programs)) is None


def test_one_program_with_the_kernel_and_one_without():
    """The predicate goes by the row count: a slice whose decode step
    keeps the plain body and whose chunk takes the kernel."""
    mixed = [PLAIN[i] if "serve_decode" in s[0] else s
             for i, s in enumerate(SCOPES)]
    rec = _record(mixed, PROGRAMS)
    assert _read(DECODE, rec) is None
    assert _read(PREFILL, rec) == pytest.approx(100.0 * 500 / 800)


@pytest.mark.parametrize("scopes", [SCOPES, PLAIN],
                         ids=["kernel", "ragged_dot"])
def test_the_expert_layers_time_counts_either_implementation(scopes):
    """``decode_moe_share`` and ``decode_moe_hbm_util`` read ``MOE``:
    the kernel by its scope path (``moe`` is a component), the custom
    call by its name. The same milliseconds either way."""
    under, all_ = scope_time.per_run_ms(_record(scopes, PROGRAMS),
                                        scope_time.MOE, "serve_decode")
    assert under * 2e6 == pytest.approx(300 + 400 + 100 + 200 + 700)
    assert all_ * 2e6 == pytest.approx(DECODE_OPS)


@pytest.mark.parametrize("name", [DECODE, PREFILL])
@pytest.mark.parametrize("kind", ["serve", "train"])
def test_recorded_slices_without_the_kernel_read_none(kind, name):
    """PR 26's recorded slices: a dense model's serve programs (no
    expert layer), and a train step with kernels of other names."""
    with gzip.open(HERE / f"recorded_{kind}_trace.json.gz", "rt") as f:
        data = json.load(f)
    assert _read(name, SimpleNamespace(
        trace=data["trace"], program=data["program"], counters={},
        window=tuple(data["window"]))) is None


def check_benchmark_json(spec, name):
    check_entry(spec, name, ["gr4h-serve-chat"], unit="%", better="lower",
                source="device_trace", layer="kernels", moves="itl_p95_ms")


def check_spec(spec):
    for name in (DECODE, PREFILL):
        check_benchmark_json(spec, name)


@pytest.mark.parametrize("name", [DECODE, PREFILL])
def test_benchmark_json_entry(name):
    check_benchmark_json(SPEC, name)
    reader = ROOT / "benchmark" / "layer_metrics" / f"{name}.py"
    assert reader.is_file() and f'"{PROGRAM[name]}"' in reader.read_text()
