"""The sparse attention's share of the HBM roofline in a decode step,
whatever implements it: the indexer's weights once, the live index keys
once and the selected K/V rows once (``shapes_sparse.
sparse_attn_step_bytes`` at the mean of the tally's ``index_scored_tokens``
and ``selected_tokens`` a decode step, every step between the slice's
first and last tally), over the chip's published bandwidth times the mean
device time a ``serve_decode`` execution spends under the scopes
``attn/index`` and ``attn/sparse``. Nothing where the program has no such
scope or the tally no such counts."""

from benchmark.lib import scope_time, shapes, shapes_sparse, tally

SPARSE = ("index", "sparse")


def read(record):
    ms = scope_time.per_run_ms(record, SPARSE, "serve_decode")
    got = tally.between(record)
    if ms is None or got is None or "sa_config" not in record.config:
        return None
    diff, _ = got
    steps = diff.get("decode_steps")
    if not steps or "selected_tokens" not in diff:
        return None
    _, peak_bytes = shapes.peak(record.device["kind"])
    need = shapes_sparse.sparse_attn_step_bytes(
        record.config, diff["index_scored_tokens"] / steps,
        diff["selected_tokens"] / steps)
    return 100.0 * need / (peak_bytes * ms[0] / 1e3)
