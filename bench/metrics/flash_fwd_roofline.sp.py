"""Roofline share of the flash forward kernels, summed over every ring step
(%).  The work is the causal algorithm's: S^2/2 score entries, two matmuls.
A forward kernel is a Mosaic call with at most five operands (positions,
q, k, v); the backward kernels take more (dout, lse, delta, ...)."""

from bench import roofline, trace


def read(rec):
    ex = rec.get("step_execs")
    if not ex:
        return None
    kernel_ns = sum(o.dur for e in ex for o in e.kernels() if trace.operand_count(o.text) <= 5)
    if not kernel_ns:
        return None
    steps = len(ex) / rec["chips"]
    work = roofline.flash_fwd(rec["S"], rec["hq"], rec["hkv"], rec["hd"])
    return 100.0 * steps * work.min_seconds(rec["peaks"]) / (kernel_ns / 1e9)
