"""Model FLOPs of the valid tokens of every step in the traced window
(attention, router, the top-k experts of each token, the LM head of each
decoded token: ``roofline_moe.serve_model_flops``), over those steps'
device time at the chip's bf16 peak (%).  Padded rows are not counted."""

from bench import roofline_moe


def read(rec):
    ticks = rec.get("ticks")
    ex = (rec.get("prefill_execs") or []) + (rec.get("decode_execs") or [])
    if not ticks or not ex or "moe_dims" not in rec or any(
            t["prefill_rows"] is None for t in ticks):
        return None
    flops = sum(roofline_moe.serve_model_flops(rec["moe_dims"], t["prefill_rows"],
                                               t["decode_contexts"]) for t in ticks)
    seconds = sum(e.dur for e in ex) / 1e9
    return 100.0 * flops / (seconds * rec["peaks"]["bf16_flops"])
