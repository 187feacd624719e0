"""Device time of one decode step program, mean over the traced window (ms)."""


def read(rec):
    ex = rec.get("decode_execs")
    if not ex:
        return None
    return sum(e.dur for e in ex) / len(ex) / 1e6
