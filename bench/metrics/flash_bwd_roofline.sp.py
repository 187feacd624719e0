"""Roofline share of the two flash backward kernels (dq, and dk with dv),
summed over every ring step (%).  The work is the causal flash backward's:
S^2/2 score entries, five matmuls (the scores are rebuilt from the lse)."""

from bench import roofline, trace


def read(rec):
    ex = rec.get("step_execs")
    if not ex:
        return None
    kernel_ns = sum(o.dur for e in ex for o in e.kernels() if trace.operand_count(o.text) > 5)
    if not kernel_ns:
        return None
    steps = len(ex) / rec["chips"]
    work = roofline.flash_bwd(rec["S"], rec["hq"], rec["hkv"], rec["hd"])
    return 100.0 * steps * work.min_seconds(rec["peaks"]) / (kernel_ns / 1e9)
