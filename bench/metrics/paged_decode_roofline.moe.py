"""Roofline share of the paged-decode kernel (%): the least time the chip
needs to read the K/V and positions in use, q and out, over the summed
time of the kernels named ``paged_decode`` inside the decode step
programs.  By name, because in a mixture-of-experts step the grouped
matmuls are Mosaic kernels too."""

from bench import roofline, roofline_moe


def read(rec):
    ticks, ex = rec.get("ticks"), rec.get("decode_execs")
    if not ticks or not ex or "moe_dims" not in rec:
        return None
    kernel_ns = sum(o.dur for e in ex for o in e.ops
                    if roofline_moe.is_named_kernel(o.text, "paged_decode"))
    if not kernel_ns:
        return None
    work = roofline.Work()
    for t in ticks:
        work = work + roofline.paged_decode(rec["moe_dims"].attention, t["decode_contexts"])
    return 100.0 * work.min_seconds(rec["peaks"]) / (kernel_ns / 1e9)
