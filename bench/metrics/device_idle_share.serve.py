"""Share of the traced window in which no op ran on the chip (%)."""


def read(rec):
    red = rec.get("reduction")
    if red is None or not red.devices or not red.window_ns:
        return None
    return 100.0 * (1.0 - red.busy_s() / red.window_s)
