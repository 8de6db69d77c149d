"""Peak bytes in use on the fullest device, read after the window and
before the reference runs, in GB (1e9 bytes)."""


def read(record):
    peak = record.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
