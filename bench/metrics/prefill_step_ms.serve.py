"""Device time of one chunked-prefill step program, mean over the traced window (ms)."""


def read(rec):
    ex = rec.get("prefill_execs")
    if not ex:
        return None
    return sum(e.dur for e in ex) / len(ex) / 1e6
