"""Share of the traced slice in which no operation ran on the device: 1 -
the union of the device's operation intervals over the slice."""
UNIT, SOURCE = "%", "device_trace"


def read(rec):
    s = rec.get("trace_summary")
    return None if not s else 100.0 * (1.0 - s["busy_s"] / s["window_s"])
