"""Roofline share of the flash kernels of the chunked-prefill steps (%):
the least time the chip needs for the causal attention of the valid chunk
tokens over their cached context, over the summed time of the kernels
named ``flash_fwd`` inside the prefill step programs.  By name, because
in a mixture-of-experts step the grouped matmuls are Mosaic kernels too."""

from bench import roofline, roofline_moe


def read(rec):
    ticks, ex = rec.get("ticks"), rec.get("prefill_execs")
    if not ticks or not ex or "moe_dims" not in rec or any(
            t["prefill_rows"] is None for t in ticks):
        return None
    kernel_ns = sum(o.dur for e in ex for o in e.ops
                    if roofline_moe.is_named_kernel(o.text, "flash_fwd"))
    if not kernel_ns:
        return None
    work = roofline.Work()
    for t in ticks:
        work = work + roofline.prefill_attention(rec["moe_dims"].attention, t["prefill_rows"])
    return 100.0 * work.min_seconds(rec["peaks"]) / (kernel_ns / 1e9)
