"""Peak device memory of the fullest chip, GB (training cells)."""


def read(record):
    return record.counters.get("peak_hbm_gb")
