"""End-to-end HBM utilisation of decoding: the bytes one whole-batch
decode step has to read (bf16 weights once, plus the live K/V of the
occupied slots at the mean context of the request list), over the
published HBM bandwidth times the median gap between tokens. Not a
kernel roofline: the gap includes the host and every other program."""

from benchmark.lib import shapes


def read(record):
    c = record.counters
    if not c.get("itl_p50_ms"):
        return None
    live = c["slot_occupancy_mean"] * c["mean_context_tokens"]
    _, peak_bytes = shapes.peak(record.device["kind"])
    return (100.0 * shapes.decode_step_bytes(record.config, live)
            / (peak_bytes * c["itl_p50_ms"] / 1e3))
