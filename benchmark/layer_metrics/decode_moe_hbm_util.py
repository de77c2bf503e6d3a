"""The expert layer's share of the HBM roofline in a decode step,
whatever implements it: router, shared MLP and the held experts once
(``shapes_hybrid.moe_step_bytes``), over the chip's published bandwidth
times the mean device time, per execution of ``serve_decode``, under the
scopes ``moe`` and ``shared_mlp`` and in the grouped products' custom
call (``scope_time.MOE_AND_SHARED``)."""

from benchmark.lib import scope_time, shapes, shapes_hybrid


def read(record):
    ms = scope_time.per_run_ms(record, scope_time.MOE_AND_SHARED,
                               "serve_decode")
    if ms is None or "layer_types" not in record.config:
        return None
    _, peak_bytes = shapes.peak(record.device["kind"])
    need = shapes_hybrid.moe_step_bytes(record.config)
    return 100.0 * need / (peak_bytes * ms[0] / 1e3)
