"""``decode_ahead_share``: the share of the traced slice's decode steps
that were dispatched with the step before still unread, from the ``ahead``
count of the ``tpu_ddp.serve.decode`` spans. On a synthetic slice (spans
with ``ahead`` 1, with 0, without the count, outside the window) and on
the recorded slices of the engine before it ran ahead, where the reader
finds nothing."""

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

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "decode_ahead_share"
DECODE = "tpu_ddp.serve.decode"


def _span(start, ahead=None, name=DECODE):
    counts = {"slots": 32, "context_tokens": 11000}
    if ahead is not None:
        counts["ahead"] = ahead
    return [name, start, 900, counts]


def _read(spans, window=(0, 10_000)):
    return run.load_module("layer_metrics", NAME).read(SimpleNamespace(
        trace={"devices": {}, "host": []},
        program={"spans": spans, "programs": {}, "scopes": {}},
        window=window))


@pytest.mark.parametrize("aheads,want", [
    ([1, 1, 1, 1], 100.0), ([0, 1, 1, 1], 75.0), ([0], 0.0),
    ([1, 0, 1, 0, 0], 40.0),
], ids=["steady", "first_step_at_rest", "only_at_rest", "mixed"])
def test_share_is_the_spans_with_ahead_one_over_those_with_the_count(
        aheads, want):
    spans = [_span(1000 * i, a) for i, a in enumerate(aheads)]
    assert _read(spans) == pytest.approx(want)


def test_only_decode_spans_whole_inside_the_window_count():
    spans = [_span(0, 1), _span(1000, 0), _span(5500, 0),   # cut by hi
             _span(2000, 0, name="tpu_ddp.serve.decode.dispatch"),
             _span(3000, 0, name="tpu_ddp.serve.step")]
    assert _read(spans, window=(0, 6000)) == pytest.approx(50.0)
    assert _read(spans, window=(500, 6000)) == pytest.approx(0.0)


def test_spans_without_the_count_are_the_engine_that_did_not_run_ahead():
    assert _read([_span(0), _span(1000)]) is None
    assert _read([]) is None
    # a slice that has both kinds reads the spans that carry it
    assert _read([_span(0), _span(1000, 1)]) == pytest.approx(100.0)


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_recorded_slices_of_the_parent_read_none(kind):
    """PR 26's recorded slices: the serve one has ``serve.decode`` spans
    with ``slots`` and ``context_tokens`` only."""
    with gzip.open(HERE / f"recorded_{kind}_trace.json.gz", "rt") as f:
        data = json.load(f)
    assert run.load_module("layer_metrics", NAME).read(SimpleNamespace(
        trace=data["trace"], program=data["program"],
        window=tuple(data["window"]))) is None


def check_spec(spec):
    """By name, wherever in the list they stand: this metric's entry, and
    PR 27's, which ``test_decode_attn_kernel_share.py`` holds too."""
    check_entry(spec, NAME, ["sc2-serve-gen"], unit="%", better="higher",
                source="device_trace", layer="serving", moves="serve_tok_s")
    check_entry(spec, "decode_attn_kernel_share", ["sc2-serve-gen"],
                unit="%", better="lower", source="device_trace",
                layer="kernels", moves="itl_p95_ms")
    names = [m["name"] for m in spec["per_layer"]]
    assert len(names) == len(set(names))


def test_benchmark_json_entries():
    check_spec(SPEC)
    assert (ROOT / "benchmark" / "layer_metrics" / f"{NAME}.py").is_file()
