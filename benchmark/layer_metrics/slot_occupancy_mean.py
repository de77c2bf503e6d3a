"""Mean number of occupied slots over the engine steps of the window:
the engine's own ``serve_slot_occupancy`` gauge times its slots."""


def read(record):
    return record.counters.get("slot_occupancy_mean")
