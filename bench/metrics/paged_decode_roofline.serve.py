"""Roofline share of the paged-decode kernel (%): the least time the chip
needs to read the K/V and positions in use, q and out, over the summed time
of the Mosaic kernels inside the decode step programs."""

from bench import roofline


def read(rec):
    ticks, ex = rec.get("ticks"), rec.get("decode_execs")
    if not ticks or not ex:
        return None
    kernel_ns = sum(o.dur for e in ex for o in e.kernels())
    if not kernel_ns:
        return None
    work = roofline.Work()
    for t in ticks:
        work = work + roofline.paged_decode(rec["dims"], t["decode_contexts"])
    return 100.0 * work.min_seconds(rec["peaks"]) / (kernel_ns / 1e9)
