"""Percent of ``serve_decode``'s device time under the scopes
``attn/index`` and ``attn/sparse``: the whole sparse attention but its
q / k / v projections, the K/V and index-key writes and the output
projection (``scope_time.share``, every step of the slice). Nothing
where the program has no such scope."""

from benchmark.lib import scope_time

SPARSE = ("index", "sparse")


def read(record):
    return scope_time.share(record, SPARSE, "serve_decode")
