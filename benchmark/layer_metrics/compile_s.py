"""Seconds spent lowering and compiling (or loading from the persistent
cache) before the window opened; ``count_compiles`` sums them."""


def read(record):
    return record.counters.get("compile_s")
