"""Causal attention FLOPs (forward two matmuls, backward four, no
recomputation) of the steps in the traced window, over the window's length
times the chips times their bf16 peak (%)."""

from bench import roofline


def read(rec):
    ex, red = rec.get("step_execs"), rec.get("reduction")
    if not ex or red is None or not red.window_ns:
        return None
    steps = len(ex) / rec["chips"]
    flops = steps * roofline.attention_train_flops(rec["S"], rec["hq"], rec["hd"])
    return 100.0 * flops / (red.window_s * rec["chips"] * rec["peaks"]["bf16_flops"])
