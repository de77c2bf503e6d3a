"""Percent of the paged K/V pool's usable blocks held by requests, mean
over the engine steps between the first and the last
``tpu_ddp.serve.tally`` of the traced slice: the tally's
``kv_blocks_in_use`` (summed over steps) over its ``steps`` times the
pool's ``kv_blocks_usable``. Memory reserved against memory in use."""

from benchmark.lib import tally


def read(record):
    got = tally.between(record)
    if got is None:
        return None
    diff, last = got
    if not diff.get("steps") or not last.get("kv_blocks_usable"):
        return None
    return (100.0 * diff["kv_blocks_in_use"]
            / (diff["steps"] * last["kv_blocks_usable"]))
