"""Share of the traced window in which the device ran no operation."""


def read(rec):
    trace = rec.get("trace")
    return None if trace is None else 1.0 - trace.busy_s / trace.window_s
