"""Sequence-parallel attention cells: back-to-back forward + backward steps.

One causal attention layer's ``sp_attention`` call, sharded over the chips
of a ``("data", "model") = (1, P)`` mesh in the layout the traffic names,
is differentiated with ``jax.vjp``: each step returns ``out`` and the
gradients of ``sum(out * g)`` for q, k and v.  Each step ends in
``block_until_ready`` before the next is issued (a closed loop).

Correctness: the last step's ``out, dq, dk, dv`` against the plain blocked
float32 attention of ``reference/attention.py``, as relative L2 errors
``||x - ref|| / ||ref||``.
"""

from __future__ import annotations

import gc
import time
from collections import Counter

import numpy as np

from bench import harness
from bench.harness import Outcome, span


def zigzag_order(S: int, P: int) -> np.ndarray:
    """Global positions in zigzag order: chip j holds chunks j and 2P-1-j."""
    C = S // (2 * P)
    return np.concatenate([
        np.concatenate([np.arange(j * C, (j + 1) * C),
                        np.arange((2 * P - 1 - j) * C, (2 * P - j) * C)])
        for j in range(P)
    ]).astype(np.int32)


def make_inputs(seed, S, hq, hkv, hd, dtype, sharding):
    """q, k, v and the output cotangent g, made on the chips from the seed."""
    import jax

    def make(key):
        ks = jax.random.split(key, 4)
        return tuple(
            jax.random.normal(k, (1, S, h, hd), jax.numpy.float32).astype(dtype)
            for k, h in zip(ks, (hq, hkv, hkv, hq))
        )

    return jax.jit(make, out_shardings=(sharding,) * 4)(jax.random.PRNGKey(seed))


def build_step(pctx, causal):
    import jax

    from repro.core.api import sp_attention

    def sp_step(q, k, v, pos, g):
        out, vjp = jax.vjp(
            lambda q, k, v: sp_attention(q, k, v, pos, pos, pctx=pctx, causal=causal),
            q, k, v,
        )
        return (out, *vjp(g.astype(out.dtype)))

    return jax.jit(sp_step)


def _setup(ctx) -> dict:
    """Mesh, inputs from the seed and the compiled step, warmed up."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.core.api import ParallelContext
    from repro.core.compat import make_mesh

    c, a, mix = ctx.config, ctx.config["attention"], ctx.mix
    P = ctx.cell["chips"]
    S, causal = mix["sequence_tokens"], mix["causal"]
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    if mix["layout"] != "zigzag" or a["layout"] != "zigzag":
        raise ValueError("sp_attention driver lays the sequence out in zigzag order")
    mesh = make_mesh((1, P), ("data", "model"), devices=ctx.devices)
    pctx = ParallelContext(
        mesh=mesh, sp_axes=("model",), data_axis="data", strategy=a["strategy"],
        layout=a["layout"], impl=a["impl"], overlap=a["overlap"],
        **a.get("blocks", {}),
    )
    spec = NamedSharding(mesh, PS("data", "model", None, None))
    order = zigzag_order(S, P)
    pos = jax.device_put(order[None], NamedSharding(mesh, PS("data", "model")))
    inputs = make_inputs(ctx.seed, S, hq, hkv, hd, jnp.dtype(mix["dtype"]), spec)
    step = build_step(pctx, causal)
    if "step" in ctx.hooks:
        step = ctx.hooks["step"](step, pctx=pctx, mesh=mesh, causal=causal)
    q, k, v, g = inputs
    args = (q, k, v, pos, g)
    jax.block_until_ready(step(*args))  # compile or load, warm up
    return dict(mesh=mesh, order=order, inputs=inputs, args=args, step=step,
                causal=causal, S=S, P=P, hq=hq, hkv=hkv, hd=hd)


def run(ctx) -> Outcome:
    import jax

    st = _setup(ctx)
    step, args, mesh = st["step"], st["args"], st["mesh"]
    S, P, causal, order = st["S"], st["P"], st["causal"], st["order"]
    c = ctx.config
    prof = harness.Profiler(ctx.cell["name"]) if ctx.trace else None
    trace_at = (0.3 * ctx.seconds, min(0.3 * ctx.seconds + 3.0, 0.9 * ctx.seconds))
    counter = harness.CompileCounter()
    counter.active = True
    n = 0
    t_start = time.perf_counter()
    t_end = t_start + ctx.seconds
    t_done = t_start
    while t_done < t_end:
        if prof is not None and not prof.on and prof.t_stop is None and t_done - t_start >= trace_at[0]:
            prof.start()
        elif prof is not None and prof.on and t_done - t_start >= trace_at[1]:
            prof.stop()
        with span("sp_step"):
            outs = jax.block_until_ready(step(*args))
        t_done = time.perf_counter()
        n += 1
    counter.active = False
    if prof is not None and prof.on:
        prof.stop()
    counter.close()
    mem_peak = harness.memory_peak_bytes(ctx.devices)
    window = t_done - t_start
    notes = [
        f"steps {n} in {window:.3f} s, {window / n * 1e3:.3f} ms per step",
        f"compilations inside the window: {counter.count}",
        f"mesh devices (ring order): {[int(d.id) for d in mesh.devices.flat]}, coords "
        f"{[tuple(getattr(d, 'coords', ())) for d in mesh.devices.flat]}",
    ]
    rec = {
        "t_window": t_start, "peaks": ctx.peaks, "chips": P,
        "S": S, "hq": st["hq"], "hkv": st["hkv"], "hd": st["hd"],
    }
    reduction = None
    if prof is not None:
        reduction, path = prof.reduce()
        notes.append(f"trace: {path}")
        rec.update(reduction=reduction,
                   step_execs=reduction.executions(lambda e: e.name == "jit_sp_step"))
        notes += kernel_notes(reduction, rec["step_execs"])

    # ---- correctness: the last step against the plain reference -----------
    got, ins = to_one_chip(outs, st["inputs"], ctx.devices[0])
    del outs, args, step, st
    gc.collect()
    errs = reference_errors(got, ins, order, causal)
    checks = {k: (v, c["correctness"][k]) for k, v in errs.items()}
    return Outcome(
        attempted=n, failed=0, e2e={"sp_tokens_per_s": n * S / window}, rec=rec,
        checks=checks, devices=list(ctx.devices), memory_peak_bytes=mem_peak,
        reduction=reduction, label=lambda e: e.name, notes=notes,
    )


def kernel_notes(reduction, step_execs) -> list:
    """What the trace held: programs by name, and the Mosaic kernels of a
    step by operand count, which is how the flash readers tell forward
    (five or fewer) from backward."""
    from bench import trace

    names = Counter(e.name for e in reduction.executions())
    arity = Counter(trace.operand_count(o.text) for e in step_execs for o in e.kernels())
    per_step = {n: c / max(len(step_execs), 1) for n, c in sorted(arity.items())}
    notes = [f"programs in the traced window (all chips): {dict(names)}",
             f"Mosaic kernels per step and chip, by operand count: {per_step}"]
    if not step_execs:
        notes.append("bench: WARNING: no jit_sp_step program in the trace; "
                     "the ring's per-layer metrics read nothing")
    return notes


def to_one_chip(outs, ins, dev):
    import jax

    return ([jax.device_put(x[0], dev) for x in outs],
            [jax.device_put(x[0], dev) for x in ins])


def reference_errors(got, ins, order, causal, *, control: bool = False) -> dict:
    """Relative L2 error of ``out, dq, dk, dv`` against the float32
    reference; ``control`` puts the float8 reference in the program's place."""
    import jax
    import jax.numpy as jnp

    from bench.reference.attention import attention_fwd_bwd

    pos = jax.device_put(jnp.asarray(order), ins[0].devices().pop())
    ref = attention_fwd_bwd(*ins, pos, pos, causal=causal)
    if control:
        got = attention_fwd_bwd(*ins, pos, pos, causal=causal, fp8=True)
    return {f"rel_err_{name}": rel_err(x, r)
            for name, x, r in zip(("out", "dq", "dk", "dv"), got, ref)}


def calibrate(ctx, seeds, control_seeds, seconds: float, steps: int = 3):
    """Readings of the compared numbers: the program on ``seeds``, the
    control on ``control_seeds``; one process, one compile.  The step runs
    ``steps`` times; ``seconds`` is not needed by a closed loop."""
    import jax

    out = []
    from types import SimpleNamespace

    for seed in sorted(set(seeds) | set(control_seeds)):
        st = _setup(SimpleNamespace(**dict(vars(ctx), seed=seed)))
        outs = None
        for _ in range(steps):
            outs = jax.block_until_ready(st["step"](*st["args"]))
        got, ins = to_one_chip(outs, st["inputs"], ctx.devices[0])
        del outs, st["args"], st["inputs"]
        row = {"seed": seed}
        if seed in seeds:
            row["program"] = reference_errors(got, ins, st["order"], st["causal"])
        if seed in control_seeds:
            row["control"] = reference_errors(got, ins, st["order"], st["causal"], control=True)
        out.append(row)
        print(row, flush=True)
    return out


def rel_err(x, ref) -> float:
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return float(jnp.linalg.norm((x - ref).ravel()) / jnp.linalg.norm(ref.ravel()))
