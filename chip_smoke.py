#!/usr/bin/env python3
"""Smoke run of the serving path and the TokenRing SP attention on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips (one v5e:2x2 host)

One chip:
  1. the Pallas kernels — flash forward, flash backward through ``jax.grad``
     and paged decode — against the float32 ``kernels/ref.py`` oracle at
     S = 2048 with Qwen3-1.7B attention widths;
  2. the full-width qwen3-1.7b (random weights from ``--seed``) served
     through ``repro.launch.serve.main`` with ``--impl pallas`` on the paged
     KV cache: 8 requests sharing a 2048-token prefix;
  3. the same requests served with ``--impl xla``: the greedy tokens of a
     request must agree over the first decode steps;
  4. the engine's two paged steps (``serving.engine.paged_steps``, the serve
     state donated) compiled at the ``qwen3-longdoc`` benchmark cell's
     shapes (the ``serving`` block of its configuration), bfloat16:
     ``memory_analysis()`` alias and temp bytes of each, and the checks that
     the whole K/V pool is aliased, so the steps update it in place, that no
     layer of it is copied, and that the prefill step reads its layer into
     VMEM.

Four chips (``--four-chips``, and nothing else): TokenRing ``sp_attention``
(zigzag layout, causal, pipelined overlap executor) on a
``("data", "model") = (1, 4)`` mesh at S = 32768, forward and ``jax.grad``,
against the unsharded Pallas flash on one chip of the same process; plus the
compiled program's collective-permute bytes per direction beside the
strategy's modeled ``comm_cost``.

Times printed here come from one cold smoke pass, compilation included: they
are not measurements.  Every check prints its bound.  Any failed phase exits
nonzero and prints no result; otherwise the last line of stdout is
``{"ok": true, "device": {...}}``.  The script refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Qwen3-1.7B attention widths.
HQ, HKV, D = 16, 8, 128
SERVE_ARGS = [
    "--arch", "qwen3-1.7b", "--page-size", "128", "--max-len", "4096",
    "--max-batch", "8", "--requests", "8", "--shared-prefix", "2048",
    "--prefill-chunk", "256", "--max-new", "32",
    # 8 requests x 17 pages of 128 tokens are in use at most; a pool of 160
    # pages (2.35 GB of bf16 K/V) leaves HBM room for the step's own copy of
    # the pool next to the float32 weights.
    "--max-pages", "160",
]
GREEDY_STEPS = 8  # decode steps over which pallas and xla tokens must agree
# The qwen3-longdoc cell's configuration: its ``serving`` block sizes the
# engine whose steps phase 4 compiles.
CELL_CONFIG = ROOT / "bench" / "configs" / "qwen3-1.7b-serve.json"
CELL_STEP_KEYS = ("max_batch", "max_len", "page_size", "prefill_chunk", "max_pages")


def _fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


class Checks:
    """Collects bounded comparisons and phase failures."""

    def __init__(self):
        self.failed: list[str] = []

    def bound(self, name, got, want, bound):
        import numpy as np

        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if got.shape != want.shape:
            return self.expect(name, False, f"shape {got.shape} vs {want.shape}")
        both_inf = np.isneginf(got) & np.isneginf(want)  # dead rows: lse = -inf
        got, want = (np.where(both_inf, 0.0, x) for x in (got, want))
        d = float(np.max(np.abs(got - want)))
        ok = np.isfinite(d) and d <= bound
        print(f"check {name}: max|d| {d:.3e}  bound {bound:.3e}  "
              f"{'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)

    def expect(self, name, ok: bool, detail: str):
        print(f"check {name}: {detail}  {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)

    def phase(self, name, fn, *args, **kw):
        t0 = time.perf_counter()
        print(f"== {name}", flush=True)
        try:
            return fn(self, *args, **kw)
        except Exception:
            traceback.print_exc()
            self.failed.append(name)
        finally:
            print(f"== {name}: {time.perf_counter() - t0:.1f} s wall (smoke run)",
                  flush=True)


def _grad_bound(ref) -> float:
    import numpy as np

    return 2e-2 * max(1.0, float(np.max(np.abs(np.asarray(ref, np.float32)))))


def check_kernels(checks, *, seed, impl="pallas", B=2, S=2048, page=128):
    """Flash fwd/bwd and paged decode vs the float32 oracle (bf16 inputs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import PAD_POS
    from repro.kernels.ops import flash_attention, paged_decode_attention
    from repro.kernels.ref import attention_reference

    f32, bf16 = jnp.float32, jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, HQ, D), f32).astype(bf16)
    k = jax.random.normal(ks[1], (B, S, HKV, D), f32).astype(bf16)
    v = jax.random.normal(ks[2], (B, S, HKV, D), f32).astype(bf16)
    g_out = jax.random.normal(ks[3], (B, S, HQ, D), f32)
    g_lse = jax.random.normal(ks[4], (B, S, HQ), f32)

    def fwd_and_grads(attn, q, k, v):
        def loss(q, k, v):  # lse feeds the loss, so the + dlse term runs too
            out, lse = attn(q, k, v)
            return jnp.sum(out.astype(f32) * g_out) + jnp.sum(lse * g_lse)

        return attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    kernel = partial(flash_attention, causal=True, impl=impl)
    oracle = partial(attention_reference, causal=True)
    (out, lse), grads = jax.jit(partial(fwd_and_grads, kernel))(q, k, v)
    with jax.default_matmul_precision("highest"):
        (ref_out, ref_lse), ref_grads = jax.jit(partial(fwd_and_grads, oracle))(
            q.astype(f32), k.astype(f32), v.astype(f32)
        )
    checks.bound(f"flash fwd out (S={S})", out, ref_out, 2e-2)
    checks.bound(f"flash fwd lse (S={S})", lse, ref_lse, 5e-2)
    for name, g, r in zip(("dq", "dk", "dv"), grads, ref_grads):
        checks.bound(f"flash bwd {name} (S={S})", g, r, _grad_bound(r))

    # Paged decode: per-request lengths around page boundaries, pages
    # assigned in reversed pool order, unmapped table tail at the sentinel.
    rng = np.random.default_rng(seed)
    lengths = np.array([S, S - 1, 1, page - 1, page, page + 1, S // 2 + 3, 3 * S // 4])
    nb = len(lengths)
    W = -(-S // page) + 1
    n_pages = sum(-(-int(L) // page) for L in lengths) + 2
    pos_pool = np.full((n_pages, page), PAD_POS, np.int32)
    bt = np.full((nb, W), n_pages, np.int32)
    free = list(range(n_pages))
    for b, L in enumerate(lengths):
        for ip in range(-(-int(L) // page)):
            pg = free.pop()
            bt[b, ip] = pg
            n = min(page, int(L) - ip * page)
            pos_pool[pg, :n] = np.arange(ip * page, ip * page + n)
    pools = [  # a one-layer stacked pool
        jnp.asarray(rng.standard_normal((1, n_pages, page, HKV, D)), bf16)
        for _ in range(2)
    ]
    qd = jnp.asarray(rng.standard_normal((nb, 1, HQ, D)), bf16)
    q_pos = (lengths - 1).astype(np.int32)[:, None]
    out, lse = jax.jit(partial(paged_decode_attention, impl=impl))(
        qd, *pools, jnp.asarray(pos_pool), jnp.asarray(bt), jnp.asarray(q_pos),
        jnp.int32(0),
    )
    mapped = bt < n_pages
    safe = np.where(mapped, bt, 0)
    k_view, v_view = (
        np.where(mapped[:, :, None, None, None], np.asarray(p[0], np.float32)[safe], 0.0)
        .reshape(nb, W * page, HKV, D)
        for p in pools
    )
    pos_view = np.where(mapped[:, :, None], pos_pool[safe], PAD_POS).reshape(nb, -1)
    with jax.default_matmul_precision("highest"):
        ref_out, ref_lse = oracle(
            qd.astype(f32), k_view, v_view, q_pos=q_pos, k_pos=pos_view
        )
    checks.bound(f"paged decode out (page={page})", out, ref_out, 2e-2)
    checks.bound(f"paged decode lse (page={page})", lse, ref_lse, 5e-2)


def serve(checks, *, impl, seed, args=None):
    """One ``launch/serve.py`` run; returns ``{uid: output tokens}``."""
    import jax

    from repro.launch import serve as launcher

    compile_s = [0.0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    args = SERVE_ARGS if args is None else args
    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    try:
        stats, done = launcher.main([*args, "--impl", impl, "--seed", str(seed)])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    wall = time.perf_counter() - t0
    busy = max(wall - compile_s[0], 1e-9)
    print(f"smoke serve[{impl}] (one cold pass, not a benchmark): "
          f"{stats['requests']} requests, {stats['tokens']} tokens, "
          f"{stats['decode_steps']} decode + {stats['prefill_steps']} prefill steps; "
          f"wall {wall:.1f} s incl. compile {compile_s[0]:.1f} s; "
          f"{stats['tokens'] / busy:.1f} tok/s outside compile")
    outputs = {r.uid: list(r.output) for r in done}
    n_req = int(args[args.index("--requests") + 1])
    max_new = int(args[args.index("--max-new") + 1])
    checks.expect(
        f"serve[{impl}] completes",
        len(outputs) == n_req and all(len(o) == max_new for o in outputs.values()),
        f"{len(outputs)}/{n_req} requests with {max_new} tokens each",
    )
    del stats, done
    gc.collect()  # the engine holds reference cycles; free its HBM now
    return outputs


def check_serving(checks, *, seed, impl="pallas", args=None):
    pallas = serve(checks, impl=impl, seed=seed, args=args)
    xla = serve(checks, impl="xla", seed=seed, args=args)
    uid = min(pallas)
    a, b = pallas[uid][:GREEDY_STEPS], xla.get(uid, [])[:GREEDY_STEPS]
    checks.expect(
        f"greedy tokens {impl} vs xla (request {uid})", a == b,
        f"first {GREEDY_STEPS} decode tokens {a} vs {b}",
    )
    same = sum(pallas[u][:GREEDY_STEPS] == xla.get(u, [])[:GREEDY_STEPS] for u in pallas)
    print(f"info: {same}/{len(pallas)} requests agree over the first "
          f"{GREEDY_STEPS} decode steps")


def check_step_memory(checks, *, impl="pallas", cfg=None, max_batch, max_len,
                      page_size, prefill_chunk, max_pages):
    """Compile the engine's two donated paged steps from shapes alone (no
    weights, no pool allocated) and print each one's ``memory_analysis()``;
    the whole K/V pool must be aliased to the step's result, no layer of it
    copied, and the prefill step must read the layer it gathers its views
    from into VMEM (``hlo_analysis.pool_layer_ops``), once for K and once
    for V: the same gather addressed into HBM made that step 40% slower."""
    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS
    from repro.core.api import ParallelContext
    from repro.launch.hlo_analysis import pool_layer_ops
    from repro.models import build_model
    from repro.serving.engine import paged_steps
    from repro.serving.kv_cache import pages_for

    if cfg is None:
        cfg = ARCHS["qwen3-1.7b"].with_(dtype="bfloat16", param_dtype="bfloat16")
    bundle = build_model(cfg, ParallelContext(mesh=None, impl=impl))
    slot_pages = pages_for(max_len, page_size)
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda: bundle.init_paged_state(max_pages, page_size, max_batch, slot_pages))
    pool_bytes = 2 * state["k"].size * state["k"].dtype.itemsize
    decode, prefill = paged_steps(bundle)
    i32 = jnp.int32
    steps = {
        "decode": (decode, jax.ShapeDtypeStruct((max_batch,), i32),
                   jax.ShapeDtypeStruct((max_batch,), jnp.bool_)),
        "prefill": (prefill, jax.ShapeDtypeStruct((max_batch, prefill_chunk), i32),
                    jax.ShapeDtypeStruct((3, max_batch), i32)),
    }
    for name, (fn, tokens, rows) in steps.items():
        compiled = fn.lower(params, tokens, state, rows).compile()
        mem = compiled.memory_analysis()
        print(f"step memory {name}: alias {mem.alias_size_in_bytes} temp "
              f"{mem.temp_size_in_bytes} argument {mem.argument_size_in_bytes} "
              f"output {mem.output_size_in_bytes} bytes; K/V pool {pool_bytes}")
        checks.expect(
            f"{name} step aliases the K/V pool", mem.alias_size_in_bytes >= pool_bytes,
            f"alias {mem.alias_size_in_bytes} >= pool {pool_bytes}",
        )
        if jax.default_backend() != "tpu":  # the VMEM read is the TPU compiler's
            continue
        ops = pool_layer_ops(compiled.as_text(), state["k"].shape)
        checks.expect(f"{name} step copies no layer of the pool", not ops["moves"],
                      f"{ops['moves']} == []")
        reads = 2 if name == "prefill" else 0
        checks.expect(
            f"{name} step reads {reads} layers into VMEM",
            len(ops["vmem_layer_reads"]) == reads,
            f"{len(ops['vmem_layer_reads'])} == {reads}",
        )


def check_tokenring(checks, *, seed, impl="pallas", S=32768, P=4):
    """TokenRing sp_attention fwd + grad on (1, P) vs one-chip flash."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.core.api import AttnShapes, ParallelContext, sp_attention
    from repro.core.compat import make_mesh, shard_map
    from repro.core.strategies import get_strategy
    from repro.core.zigzag import zigzag_positions
    from repro.kernels.ops import flash_attention
    from repro.launch.hlo_analysis import analyze_hlo

    f32, bf16 = jnp.float32, jnp.bfloat16
    mesh = make_mesh((1, P), ("data", "model"))
    pctx = ParallelContext(
        mesh=mesh, sp_axes=("model",), data_axis="data", strategy="tokenring",
        layout="zigzag", impl=impl, overlap=True,
    )
    # Global sequence in zigzag order: rank j holds chunks j and 2P-1-j.
    # Masking is by position, so the one-chip reference takes the same arrays.
    pos = jnp.concatenate([zigzag_positions(S, P, j) for j in range(P)])[None]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, S, HQ, D), f32).astype(bf16)
    k = jax.random.normal(ks[1], (1, S, HKV, D), f32).astype(bf16)
    v = jax.random.normal(ks[2], (1, S, HKV, D), f32).astype(bf16)
    g = jax.random.normal(ks[3], (1, S, HQ, D), f32)

    qspec, pspec = PS("data", "model", None, None), PS("data", "model")
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    ring_args = (put(q, qspec), put(k, qspec), put(v, qspec), put(pos, pspec))

    def ring_out(q, k, v, p):
        return sp_attention(q, k, v, p, p, pctx=pctx, causal=True)

    ring_lse = shard_map(
        partial(
            get_strategy("tokenring").fn, axis_name="model", causal=True,
            impl=impl, overlap=True, return_lse=True,
        ),
        mesh=mesh, in_specs=(qspec, qspec, qspec, pspec, pspec),
        out_specs=(qspec, PS("data", "model", None)),
    )

    def loss(attn, q, k, v, p):
        return jnp.sum(attn(q, k, v, p).astype(f32) * g)

    fwd = jax.jit(ring_out).lower(*ring_args).compile()
    out = fwd(*ring_args)
    _, lse = jax.jit(lambda q, k, v, p: ring_lse(q, k, v, p, p))(*ring_args)
    grads = jax.jit(jax.grad(partial(loss, ring_out), argnums=(0, 1, 2)))(*ring_args)

    one = jax.devices()[0]
    ref_args = [jax.device_put(x, one) for x in (q, k, v, pos)]

    def ref_attn(q, k, v, p):
        return flash_attention(q, k, v, q_pos=p, k_pos=p, causal=True, impl=impl)

    ref_out, ref_lse = jax.jit(ref_attn)(*ref_args)
    ref_grads = jax.jit(jax.grad(
        partial(loss, lambda *a: ref_attn(*a)[0]), argnums=(0, 1, 2)
    ))(*ref_args)

    checks.bound(f"tokenring out (S={S}, P={P})", out, ref_out, 2e-2)
    checks.bound(f"tokenring lse (S={S}, P={P})", lse, ref_lse, 5e-2)
    for name, gr, r in zip(("dq", "dk", "dv"), grads, ref_grads):
        checks.bound(f"tokenring {name} (S={S}, P={P})", gr, r, _grad_bound(r))

    st = analyze_hlo(fwd.as_text(), world=P)
    plan = pctx.plan(
        AttnShapes(B=1, Sq=S, Hq=HQ, Hkv=HKV, D=D, dtype_bytes=2), causal=True
    )
    # The model prices q/out/lse; the measured permutes also carry the int32
    # positions of each traveling query half: (P - 1) hops of S/P/2 rows.
    pos_bytes = (P - 1) * (S // P // 2) * 4
    print(f"collective-permute bytes per direction (compiled forward HLO): "
          f"fwd {st.link_bytes_fwd:.0f}, bwd {st.link_bytes_bwd:.0f}; "
          f"comm_cost fwd {plan.cost.fwd_bytes:.0f}, bwd {plan.cost.bwd_bytes:.0f} "
          f"(+{pos_bytes} B of positions)")
    for name, got, want in (("fwd", st.link_bytes_fwd, plan.cost.fwd_bytes),
                            ("bwd", st.link_bytes_bwd, plan.cost.bwd_bytes)):
        checks.expect(
            f"permute bytes {name} == comm_cost + positions",
            got == want + pos_bytes, f"{got:.0f} vs {want + pos_bytes:.0f}",
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip TokenRing phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}: run from a checkout", 2)
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"needs a TPU, found platform {dev.platform!r} "
              f"({len(devices)} {dev.device_kind} device(s))")
    if args.four_chips and len(devices) < 4:
        _fail(f"--four-chips needs 4 TPU devices, found {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.device_kind} ({dev.platform}) x{len(devices)}; "
          f"compile cache {enable_compile_cache()}")
    checks = Checks()
    if args.four_chips:
        checks.phase("tokenring sp_attention on 4 chips vs one-chip flash",
                     check_tokenring, seed=args.seed)
    else:
        checks.phase("kernels vs float32 oracle", check_kernels, seed=args.seed)
        checks.phase("serve qwen3-1.7b: pallas, then xla", check_serving,
                     seed=args.seed)
        serving = json.loads(CELL_CONFIG.read_text())["serving"]
        checks.phase("paged steps at the qwen3-longdoc cell's shapes",
                     check_step_memory, **{k: serving[k] for k in CELL_STEP_KEYS})
    if checks.failed:
        _fail(f"FAILED: {', '.join(checks.failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
