"""Percent of the live context that the selection lets attention read,
over every decode step between the slice's first and last tally: the
tally's ``selected_tokens`` (min(length + 1, topk) summed over the rows)
over its ``index_scored_tokens`` (length + 1 summed over the rows: the
positions the indexer scored). 100 where every context is within the
top-k, so the cell has left the sparse regime. Nothing where the tally
lacks the counts (a model without a learned sparse attention, the
parent)."""

from benchmark.lib import tally


def read(record):
    return tally.ratio(record, "selected_tokens", "index_scored_tokens",
                       100.0)
