"""Roofline share of the attention kernels of the chunked-prefill steps (%):
the least time the chip needs for the causal attention of the valid chunk
tokens over their cached context, over the summed time of the Mosaic kernels
inside the prefill step programs."""

from bench import roofline


def read(rec):
    ticks, ex = rec.get("ticks"), rec.get("prefill_execs")
    if not ticks or not ex or any(t["prefill_rows"] is None for t in ticks):
        return None
    kernel_ns = sum(o.dur for e in ex for o in e.kernels())
    if not kernel_ns:
        return None
    work = roofline.Work()
    for t in ticks:
        work = work + roofline.prefill_attention(rec["dims"], t["prefill_rows"])
    return 100.0 * work.min_seconds(rec["peaks"]) / (kernel_ns / 1e9)
