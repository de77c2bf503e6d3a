"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It knows no cell, configuration, runner or metric by name. A cell is an
entry of ``BENCHMARK.json``'s ``workloads``; its configuration is the
``file`` of the entry of ``configs`` it names; its traffic mix is
``traffic/<traffic>.json``, whose ``runner`` names
``runners/<runner>.py``; after a traced run, each per-layer metric the
cell reports is read by ``layer_metrics/<metric>.py``. All are found by
name at run time, so a later PR adds any of them as new files and new
entries, and edits nothing that is here.

A runner is a module with ``run(bench) -> dict``. It builds the system
under test from ``bench.config`` / ``bench.traffic`` / ``bench.seed`` on
``bench.devices``, warms up every shape it will use, calls
``bench.open_window()``, measures for ``bench.seconds`` (calling
``bench.tick()`` at least once a step and wrapping host work in
``bench.span("bench.<what>")``), calls ``bench.close_window()`` and
returns ``{"correct", "attempted", "failed", "values": {metric: value},
"counters": {...}}``. A per-layer metric is a module with
``read(record) -> float | None`` (``record.trace``, ``.counters``,
``.values``, ``.cell``, ``.config``, ``.traffic``, ``.device``,
``.window``); one that finds nothing to read returns None and is left out.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Earlier lines are for people. Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # as near to process start as code gets

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:       # run as a script, sys.path[0] is benchmark/
    sys.path.insert(0, str(ROOT))

from benchmark.lib import trace as trace_lib  # noqa: E402

SPEC = ROOT / "BENCHMARK.json"
# Where traffic/, runners/ and layer_metrics/ are looked up, in order.
SEARCH = [Path(__file__).resolve().parent]
TRACE_DIR = ROOT / ".bench_trace"
PLATFORM = "tpu"
TRACE_SECONDS = 5.0     # the traced slice: the end of the window
TRACED_SPAN = "bench.traced"


class BenchError(Exception):
    """The run cannot give a result; exit 2, print none."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str, suffix: str) -> Path:
    for base in SEARCH:
        path = base / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise BenchError(f"no {kind}/{name}{suffix} under "
                     f"{[str(b) for b in SEARCH]}")


def load_module(kind: str, name: str):
    path = find(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchError(f"BENCHMARK.json has no {what} {name!r}")


def metrics_of(spec: dict, section: str, cell: str) -> list:
    """The metrics of ``section`` that ``cell`` reports."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def check_devices(chips: int) -> list:
    """The ``chips`` devices the cell runs on; refuses anything but TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        raise BenchError(f"JAX found platform {devices[0].platform!r}, "
                         f"not {PLATFORM!r}: nothing is measured")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips and JAX "
                         f"found {len(devices)}")
    return devices[:chips]


class Bench:
    """What a runner sees of the harness: the cell's data, the window,
    the spans."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, devices):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.setup_s = None
        self.setup_split: dict = {}
        self.t_open = self.t_close = None
        self.compiles = None
        self.all_compiles = None    # set by measure(): the whole run's
        self.compile_s = None       # compile seconds before the window
        self._mark = _T0
        self._traced = None         # the open "bench.traced" span
        self._stack = contextlib.ExitStack()

    # -- set-up -------------------------------------------------------------

    def phase(self, name: str) -> None:
        """Close one part of set-up (data, init, warm-up, check ...):
        the seconds since the last call go under ``name``."""
        now = time.perf_counter()
        self.setup_split[name] = round(
            self.setup_split.get(name, 0.0) + now - self._mark, 3)
        self._mark = now

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    # -- the measured window ------------------------------------------------

    def open_window(self) -> float:
        from tpu_ddp.analysis.retrace import count_compiles

        self.phase("rest")
        self.compiles = self._stack.enter_context(
            count_compiles(ignore=frozenset()))
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - _T0
        self.compile_s = self.all_compiles.compile_seconds
        return self.t_open

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_open

    def tick(self, unit_s: float = 0.0) -> None:
        """Called by the runner before each unit of work (a step, a
        whole epoch of about ``unit_s`` seconds), with no span of its
        own open: starts the profiler when the window has
        ``TRACE_SECONDS`` left, or one unit if that is longer. The
        traced slice is one span, ``bench.traced``, from here to the
        close of the window."""
        if (self.trace and self.t_open is not None and self._traced is None
                and self.elapsed()
                >= self.seconds - max(TRACE_SECONDS, unit_s)):
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self._traced = self.span(TRACED_SPAN)
            self._traced.__enter__()

    def close_window(self) -> float:
        self.t_close = time.perf_counter()
        if self._traced is not None:
            self._traced.__exit__(None, None, None)
        self._stack.close()
        return self.t_close

    def stop_trace(self):
        """After the window: write the trace out and load it."""
        if self._traced is None:
            return None
        import jax

        jax.profiler.stop_trace()
        return trace_lib.load_xplane(str(TRACE_DIR))


def device_record(devices, trace, window) -> dict:
    # (a backend that reports no memory gives 0, which no floor admits)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace is not None:
        lo, hi = window
        rec["busy_s"] = trace_lib.busy_seconds(trace, lo, hi)
        rec["window_s"] = (hi - lo) / 1e9
    return rec


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        line = measure(args)
    except BenchError as e:
        print(f"[benchmark] {e}", file=sys.stderr)
        return 2
    print(json.dumps(line))
    return 0


def measure(args) -> dict:
    spec = load_json(SPEC)
    cell = by_name(spec["workloads"], args.workload, "workload")
    config_entry = by_name(spec["configs"], cell["config"], "configuration")
    config = load_json(ROOT / config_entry["file"])
    traffic = load_json(find("traffic", cell["traffic"], ".json"))
    try:
        import jax

        from tpu_ddp.analysis.retrace import count_compiles
        from tpu_ddp.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        raise BenchError(f"the program under test is not here: {e}") from e

    # The program's own cache rule: JAX_COMPILATION_CACHE_DIR if set,
    # else <checkout>/.jax_cache. Every program is kept, however fast it
    # compiled, so that a second run compiles nothing.
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = check_devices(cell["chips"])
    runner = load_module("runners", traffic["runner"])
    bench = Bench(cell, config, traffic, args.seed, args.seconds,
                  bool(args.trace), devices)
    bench.phase("import")
    with count_compiles(ignore=frozenset()) as bench.all_compiles:
        result = runner.run(bench)
    if bench.t_open is None or bench.t_close is None:
        raise BenchError(f"runner {traffic['runner']!r} measured no window")
    trace = bench.stop_trace()

    counters = dict(result.get("counters", {}))
    counters["compile_s"] = bench.compile_s
    counters["compiles_in_window"] = sum(bench.compiles.counts.values())
    values = dict(result["values"], setup_s=bench.setup_s)
    correct = bool(result["correct"]) and not counters["compiles_in_window"]

    if args.trace and trace is None:
        raise BenchError("the window closed before the profiler started")
    window = trace_lib.span_window(trace, TRACED_SPAN) if trace else None
    device = device_record(devices, trace, window)
    counters["peak_hbm_gb"] = device["memory_peak_bytes"] / 1e9

    section = "per_layer" if args.trace else "end_to_end"
    wanted = metrics_of(spec, section, cell["name"])
    metrics: dict = {}
    if args.trace:
        record = SimpleNamespace(
            trace=trace, window=window, counters=counters, values=values,
            cell=cell, config=config, traffic=traffic,
            device={"kind": devices[0].device_kind, "count": len(devices)})
        for m in wanted:
            value = load_module("layer_metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in wanted:
            if m["name"] not in values:
                raise BenchError(f"runner {traffic['runner']!r} did not "
                                 f"measure {m['name']!r}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    print(json.dumps({"cell": cell["name"], "seed": args.seed,
                      "seconds": args.seconds,
                      "window_s": bench.t_close - bench.t_open,
                      "setup_split_s": bench.setup_split,
                      "compiles_in_window": bench.compiles.counts,
                      "values": values, "counters": counters,
                      "notes": result.get("notes", {})}, default=str))
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if trace is not None:
        line["breakdown"] = {
            "device_ops": trace_lib.top_ops(trace, *window),
            "idle_gaps": trace_lib.idle_gaps(trace, *window)}
    return line


if __name__ == "__main__":
    sys.exit(main())
