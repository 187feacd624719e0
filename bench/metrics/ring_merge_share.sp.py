"""Share of the ring step programs' device time spent in ops whose
name-scope path holds ``ring_merge`` (the partial (out, lse) merges of the
forward, and their transposes in the backward), over every chip (%)."""

from bench import program_trace

RING_SCOPES = ("ring_send", "ring_compute", "ring_merge")


def read(rec):
    ex = rec.get("step_execs")
    pt = program_trace.load(rec, scopes=True) if ex else None
    if pt is None:
        return None
    mine = {id(e) for e in ex}
    total = merge = 0
    scoped = False
    for d in rec["reduction"].devices:
        for e in d.executions:
            if id(e) not in mine:
                continue
            total += e.dur
            for o in e.ops:
                path = pt.scope(d.name, o)
                scoped = scoped or any(r in path for r in RING_SCOPES)
                if "ring_merge" in path:
                    merge += o.dur
    if not scoped or not total:
        return None  # a program without the ring's scopes
    return 100.0 * merge / total
