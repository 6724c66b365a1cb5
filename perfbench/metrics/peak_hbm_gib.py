"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, after the
window."""
UNIT, SOURCE = "GiB", "program_counter"


def read(rec):
    peak = rec.get("memory_peak_bytes")
    return None if peak is None else peak / 2**30
