"""Percent of the device time of ``serve_decode``'s operations, in the
traced slice, spent moving K/V: operations whose ``named_scope`` path
holds ``kv_gather`` (the pool gathered through the block tables) or
``kv_write`` (the new K/V scattered into the pool). A fusion counts under
the scope of its root instruction, so the share of the time that carries
no scope at all is printed beside it, in a detail line of the run."""

import json

from benchmark.lib import program_trace


def read(record):
    shares = program_trace.scope_share(
        record, ("kv_gather", "kv_write"), program="serve_decode")
    if shares is None:
        return None
    print(json.dumps({"decode_kv_move_share": shares[0],
                      "serve_decode_unscoped_share": shares[1]}))
    return shares[0]
