"""Share of the chunked-prefill steps' token slots (``max_batch x chunk``
rows of a step) that held prompt tokens, over the steps the traced window
dispatched (%).  Read from the engine's ``engine.prefill`` spans, which note
each step's valid and padded tokens."""

from bench import program_trace


def read(rec):
    pt = program_trace.load(rec)
    red = rec.get("reduction")
    if pt is None or red is None:
        return None
    steps = [s for s in program_trace.spans_in(pt, "engine.prefill", red.window)
             if "padded_tokens" in s.meta]
    padded = sum(s.meta["padded_tokens"] for s in steps)
    if not padded:
        return None
    return 100.0 * sum(s.meta["valid_tokens"] for s in steps) / padded
