"""Programs compiled while the window was open. Anything but 0 also makes
the run not ``correct``."""


def read(record):
    return record.counters.get("compiles_in_window")
