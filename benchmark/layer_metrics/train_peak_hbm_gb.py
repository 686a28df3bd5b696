"""Peak device memory after the window, memory_stats()["peak_bytes_in_use"]
of the fullest chip, in GB (1e9 bytes)."""


def read(record, trace):
    peak = record.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
