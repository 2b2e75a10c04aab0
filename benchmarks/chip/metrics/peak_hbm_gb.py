"""Device: memory_stats()["peak_bytes_in_use"] after the window, in GB."""


def read(rec):
    b = rec.get("memory_peak_bytes")
    return None if b is None else b / 1e9
