"""Share of the step programs' device time in which a collective runs and no
other op does (%), over every chip."""

from bench import trace


def read(rec):
    ex = rec.get("step_execs")
    if not ex:
        return None
    total = sum(e.dur for e in ex)
    exposed = sum(trace.exposed_collective_ns(e.ops) for e in ex)
    return 100.0 * exposed / total if total else None
