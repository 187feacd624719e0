"""Share of the step programs' device time spent in the expert layer (%):
ops whose name-scope path holds ``moe_route`` (router, top-k, sort) or
``moe_combine`` (unsort, weighted sum), and the grouped-matmul ops
(``roofline_moe.is_gmm``)."""

from bench import program_trace, roofline_moe

MOE_SCOPES = ("moe_route", "moe_combine")


def read(rec):
    ex = (rec.get("prefill_execs") or []) + (rec.get("decode_execs") or [])
    pt = program_trace.load(rec, scopes=True) if ex else None
    if pt is None:
        return None
    mine = {id(e) for e in ex}
    total = moe = 0
    scoped = False
    for d in rec["reduction"].devices:
        for e in d.executions:
            if id(e) not in mine:
                continue
            total += e.dur
            for o in e.ops:
                path = pt.scope(d.name, o)
                routed = any(s in path for s in MOE_SCOPES)
                scoped = scoped or routed
                if routed or roofline_moe.is_gmm(path, o.text):
                    moe += o.dur
    if not scoped or not total:
        return None  # a program without the expert layer's scopes
    return 100.0 * moe / total
