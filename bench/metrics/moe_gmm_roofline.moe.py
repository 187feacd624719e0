"""Roofline share of the expert layer's grouped matmuls (%): the least time
the chip needs for the window's routed expert rows and touched experts
(the expert layer's counters over the traced window, ``roofline_moe``),
over the device time of the step programs' grouped-matmul ops
(``roofline_moe.is_gmm``: the ``moe_gmm`` scope and the ``ragged-dot``
kernels)."""

from bench import program_trace, roofline_moe


def read(rec):
    counts = rec.get("moe_counts")
    ex = (rec.get("prefill_execs") or []) + (rec.get("decode_execs") or [])
    pt = program_trace.load(rec, scopes=True) if counts and ex else None
    if pt is None:
        return None
    mine = {id(e) for e in ex}
    ns = sum(o.dur for d in rec["reduction"].devices for e in d.executions if id(e) in mine
             for o in e.ops if roofline_moe.is_gmm(pt.scope(d.name, o), o.text))
    if not ns:
        return None
    work = roofline_moe.expert_work(rec["moe_dims"], counts["routed"], counts["touched"])
    return 100.0 * work.min_seconds(rec["peaks"]) / (ns / 1e9)
