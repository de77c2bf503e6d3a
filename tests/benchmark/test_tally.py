"""The readers of the serving engine's tally (``benchmark/lib/tally.py``:
``serve_dry_step_share``, ``engine_host_busy_ms_per_step``,
``kv_pool_used_share``) and of the trainer's ``dry`` count
(``train_dry_step_share``), on synthetic program dicts: the first and the
last tally inside the window, fewer than two, counts absent as on the
parent's program, and on the recorded slices of a program that had
neither."""

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
TALLY = "tpu_ddp.serve.tally"
SERVE = ["serve_dry_step_share", "engine_host_busy_ms_per_step",
         "kv_pool_used_share"]
NEW = SERVE + ["train_dry_step_share"]


def _tally(start, k):
    """The totals after ``k`` steps of an engine whose every step
    decodes ahead, one step in four dry, 7 ms of host work, 300 of its
    800 blocks held."""
    return [TALLY, start, 0, {
        "steps": k, "decode_steps": k, "decode_ahead": k,
        "dry_steps": k // 4, "decode_rows": 32 * k,
        "context_tokens": 13_000 * k, "prefill_chunks": k // 6,
        "prefill_tokens": 256 * (k // 6), "kv_blocks_in_use": 300 * k,
        "kv_blocks_usable": 800, "host_busy_ms": 7.0 * k,
        "fetch_wait_ms": 7.5 * k, "queue_depth": 0}]


def _read(name, spans, window=(0, 10_000)):
    return run.load_module("layer_metrics", name).read(SimpleNamespace(
        trace={"devices": {}, "host": []},
        program={"spans": spans, "programs": {}, "scopes": {}},
        window=window))


@pytest.mark.parametrize("name, want", [
    ("serve_dry_step_share", 25.0), ("engine_host_busy_ms_per_step", 7.0),
    ("kv_pool_used_share", 37.5)])
def test_the_first_and_last_tally_in_the_window_give_the_mean(name, want):
    # tallies at steps 0 (outside), 40, 80, 120 (cut by the window's end)
    spans = [_tally(0, 0), _tally(1000, 40), _tally(5000, 80),
             _tally(9000, 120),
             ["tpu_ddp.serve.step", 2000, 500, {}]]
    assert _read(name, spans, window=(500, 4000)) is None   # one inside
    assert _read(name, spans, window=(500, 9500)) == pytest.approx(want)
    assert _read(name, spans) == pytest.approx(want)


@pytest.mark.parametrize("name", SERVE)
def test_fewer_than_two_tallies_read_nothing(name):
    assert _read(name, []) is None
    assert _read(name, [_tally(1000, 40)]) is None
    # the parent's program: steps and decodes, no tally
    assert _read(name, [["tpu_ddp.serve.step", 0, 500, {}],
                        ["tpu_ddp.serve.decode", 100, 300,
                         {"context_tokens": 5, "ahead": 1}]]) is None


def test_a_slice_in_which_nothing_moved_reads_nothing():
    still = [_tally(1000, 40), _tally(5000, 40)]
    for name in SERVE:
        assert _read(name, still) is None


def test_dry_share_counts_only_the_steps_dispatched_ahead():
    a, b = _tally(1000, 40), _tally(5000, 80)
    b[3]["decode_ahead"] = 70       # ten of the forty at rest
    b[3]["dry_steps"] = 17          # seven dry of thirty ahead
    assert _read("serve_dry_step_share", [a, b]) == pytest.approx(70.0 / 3)


def _train(start, **counts):
    return ["tpu_ddp.lm.train_step", start, 200, {"step": start, **counts}]


def test_train_dry_share_is_the_spans_with_dry_one():
    spans = [_train(0), _train(1000, dry=0), _train(2000, dry=1),
             _train(3000, dry=0), _train(4000, dry=0),
             _train(9950, dry=1)]          # cut by the window's end
    assert _read("train_dry_step_share", spans) == pytest.approx(25.0)
    # the parent's program: no span carries the count
    assert _read("train_dry_step_share", [_train(0), _train(1000)]) is None
    assert _read("train_dry_step_share", []) is None


@pytest.mark.parametrize("kind", ["serve", "train"])
@pytest.mark.parametrize("name", NEW)
def test_recorded_slices_of_an_older_program_read_none(kind, name):
    with gzip.open(HERE / f"recorded_{kind}_trace.json.gz", "rt") as f:
        data = json.load(f)
    assert run.load_module("layer_metrics", name).read(SimpleNamespace(
        trace=data["trace"], program=data["program"],
        window=tuple(data["window"]))) is None


def check_benchmark_json(spec, name):
    """The entry by its name, wherever it stands in ``per_layer``; its
    cells by membership, so that a later entry or cell changes nothing
    here."""
    serve = name in SERVE
    check_entry(
        spec, name,
        ["sc2-serve-gen", "gr4h-serve-chat"] if serve else ["sc2-train-s4k"],
        unit="ms" if "_ms_" in name else "%",
        better="higher" if name == "kv_pool_used_share" else "lower",
        source="program_counter",
        layer="serving" if serve else "train loop",
        moves={"serve_dry_step_share": "itl_p95_ms",
               "train_dry_step_share": "train_throughput"}.get(
                   name, "serve_tok_s"))


def check_spec(spec):
    for name in NEW:
        check_benchmark_json(spec, name)


@pytest.mark.parametrize("name", NEW)
def test_benchmark_json_entry(name):
    check_benchmark_json(SPEC, name)
