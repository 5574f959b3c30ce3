"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window, on the
fullest chip, in GB (1e9 bytes)."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
