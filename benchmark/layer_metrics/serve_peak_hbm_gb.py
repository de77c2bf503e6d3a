"""Peak device memory of the fullest chip, GB (serving cells)."""


def read(record):
    return record.counters.get("peak_hbm_gb")
