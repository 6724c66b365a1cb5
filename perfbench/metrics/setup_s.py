"""Process start to the first scored instant: imports, model from the seed,
reference check, pool, warm-up of the cell's own shapes (compiles or cache
reads), ramp."""
UNIT, SOURCE = "s", "host_clock"


def read(rec):
    return rec["setup_s"]
