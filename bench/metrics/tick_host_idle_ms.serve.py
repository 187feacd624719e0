"""Chip-idle time inside the engine's own spans (``engine.*``: the host was
in one of the engine's phases and the chip had nothing to run), per
``engine.tick`` span of the traced window (ms)."""

from bench import program_trace


def read(rec):
    pt = program_trace.load(rec)
    red = rec.get("reduction")
    if pt is None or red is None or not red.devices:
        return None
    ticks = program_trace.spans_in(pt, "engine.tick", red.window)
    if not ticks:
        return None
    idle = program_trace.phase_idle(red, pt)
    ns = sum(v for k, v in idle.items() if k.startswith(program_trace.PROGRAM_PREFIX))
    return ns / len(red.devices) / len(ticks) / 1e6
