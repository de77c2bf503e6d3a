"""Percent of ``serve_decode``'s device time under the scope
``attn/index``: the indexer's projections, the scores of every cached
position and the top-k selection (``scope_time.share``, every step of
the slice). Nothing where the program has no such scope (a model
without a learned sparse attention, the parent)."""

from benchmark.lib import scope_time

INDEX = ("index",)


def read(record):
    return scope_time.share(record, INDEX, "serve_decode")
