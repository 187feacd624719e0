"""Multi-device SP-strategy correctness checks (run as ``python -m``).

Verifies, on 8 simulated host devices, that every sequence-parallel strategy
(ring, ring_bidir, tokenring, tokenring_faithful, ulysses, multi-pod hybrid,
decode, chunked prefill, recurrence) matches the single-device oracle —
forward AND gradients — under zigzag and contiguous layouts, MHA and GQA.

Usage:  PYTHONPATH=src python -m repro.testing.strategy_check [check ...]
Prints ``PASS <name>`` per check; non-zero exit on any failure.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_CHECK_DEVICES", "8")
    + " "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import ParallelContext, sp_attention, sp_decode, sp_scan  # noqa: E402
from repro.core.compat import make_mesh  # noqa: E402
from repro.core.zigzag import to_zigzag  # noqa: E402
from repro.kernels.flash_attention import PAD_POS  # noqa: E402
from repro.kernels.ref import attention_reference  # noqa: E402

TOL = dict(atol=2e-4, rtol=2e-4)


def _data(B=2, S=256, Hq=4, Hkv=4, D=32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    return q, k, v


def _layout(x, P_sp, layout):
    return to_zigzag(x, P_sp, axis=1) if layout == "zigzag" else x


def _positions(S, P_sp, layout):
    pos = jnp.arange(S, dtype=jnp.int32)
    if layout == "zigzag":
        pos = to_zigzag(pos[None, :, None], P_sp, axis=1)[0, :, 0]
    return pos


def check_strategies():
    from repro.core.strategies import ineligible_reason, registered_strategies

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    for desc in registered_strategies():
        for layout, causal, (Hq, Hkv) in [
            ("zigzag", True, (4, 4)),
            ("zigzag", True, (8, 4)),
            ("contig", False, (4, 4)),
        ]:
            strategy = desc.name
            if ineligible_reason(desc, Hq=Hq, Hkv=Hkv, P=4, layout=layout) is not None:
                continue
            pctx = ParallelContext(
                mesh=mesh, sp_axes=("model",), strategy=strategy,
                layout=layout, impl="xla", block_q=64, block_k=64,
            )
            q, k, v = _data(Hq=Hq, Hkv=Hkv, seed=hash((strategy, layout)) % 2**31)
            S = q.shape[1]
            ref, _ = attention_reference(q, k, v, causal=causal)
            qz, kz, vz = (_layout(x, 4, layout) for x in (q, k, v))
            pos = _positions(S, 4, layout)
            out = jax.jit(
                lambda q, k, v, p: sp_attention(
                    q, k, v, p, p, pctx=pctx, causal=causal
                )
            )(qz, kz, vz, pos)
            ref_l = _layout(ref, 4, layout)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref_l), **TOL)
            print(f"PASS strategy={strategy} layout={layout} Hq={Hq} Hkv={Hkv}")


def check_gradients():
    """``jax.grad`` of every registered (non-serving) SP strategy against the
    oracle's autodiff — tokenring bidir + faithful, ring, ring_bidir, ulysses,
    window — at whatever device count the subprocess was launched with
    (``REPRO_CHECK_DEVICES``: 4 and 8 in CI).  Exercises the full backward
    stack: flash custom_vjp (tile-skipped XLA bwd) differentiated through
    each strategy's ppermute/all-to-all schedule inside shard_map.
    """
    from repro.core.strategies import ineligible_reason, registered_strategies

    n_dev = len(jax.devices())
    P_sp = 4
    mesh = make_mesh((n_dev // P_sp, P_sp), ("data", "model"))
    Hq, Hkv, W = 8, 4, 96
    q, k, v = _data(Hq=Hq, Hkv=Hkv, seed=7)
    S = q.shape[1]
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def ref_grads(window):
        def ref_loss(q, k, v):
            out, _ = attention_reference(q, k, v, causal=True, window=window)
            return jnp.sum(out * w)

        return jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)

    g_ref = {None: ref_grads(None), W: ref_grads(W)}

    checked = 0
    for desc in registered_strategies():
        if desc.serving_side:
            continue
        if desc.ring_axes != 1:
            # hierarchical schedules bind via plan(topology=...) over a
            # (pod, inner) mesh — numeric cell in check_hybrid
            continue
        window = W if desc.requires_window else None
        layout = desc.requires_layout or "zigzag"
        why = ineligible_reason(
            desc, Hq=Hq, Hkv=Hkv, P=P_sp, layout=layout, window=window
        )
        assert why is None, f"{desc.name} unexpectedly ineligible: {why}"
        pctx = ParallelContext(
            mesh=mesh, sp_axes=("model",), strategy=desc.name, layout=layout,
            impl="xla", block_q=64, block_k=64, block_q_bwd=32, block_k_bwd=32,
        )
        pos = _positions(S, P_sp, layout)
        w_l = _layout(w, P_sp, layout)

        def sp_loss(q, k, v):
            ql, kl, vl = (_layout(x, P_sp, layout) for x in (q, k, v))
            out = sp_attention(
                ql, kl, vl, pos, pos, pctx=pctx, causal=True, window=window
            )
            return jnp.sum(out * w_l)

        g = jax.jit(jax.grad(sp_loss, argnums=(0, 1, 2)))(q, k, v)
        for a, b, nm in zip(g, g_ref[window], "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                err_msg=f"{desc.name} d{nm}",
            )
        checked += 1
        print(
            f"PASS gradients strategy={desc.name} layout={layout} "
            f"window={window} ({n_dev} devices)"
        )
    assert checked >= 6, f"only {checked} strategies gradient-checked"


def check_hybrid():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    # ulysses as hybrid inner: head divisibility is judged at the intra-pod
    # degree (2), not the total SP degree (4) — Hkv=2 % 2 == 0 is legal.
    for inner in ["tokenring", "ring", "ulysses"]:
        pctx = ParallelContext(
            mesh=mesh, sp_axes=("pod", "model"), strategy="tokenring",
            inner_strategy=inner, impl="xla", block_q=32, block_k=32,
        )
        q, k, v = _data(B=2, S=256, Hq=4, Hkv=2, D=16, seed=11)
        S = q.shape[1]
        P_sp = 4  # pod * model
        ref, _ = attention_reference(q, k, v, causal=True)
        qz, kz, vz = (to_zigzag(x, P_sp, axis=1) for x in (q, k, v))
        pos = _positions(S, P_sp, "zigzag")
        out = jax.jit(
            lambda q, k, v, p: sp_attention(q, k, v, p, p, pctx=pctx, causal=True)
        )(qz, kz, vz, pos)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(to_zigzag(ref, P_sp, axis=1)), **TOL
        )
        print(f"PASS hybrid inner={inner} (2 pods x 2 sp)")

    # Hierarchical 2D TokenRing on the same (pod=2, model=2) mesh, bound
    # through the graph-aware planner: intra-pod bidirectional co-rotation,
    # inter-pod pipelined KV exchange (core/hier2d.py).
    from repro.core.api import AttnShapes
    from repro.core.topology import two_pods

    pctx = ParallelContext(
        mesh=mesh, sp_axes=("pod", "model"), strategy="tokenring2d",
        impl="xla", block_q=32, block_k=32,
    )
    q, k, v = _data(B=2, S=256, Hq=4, Hkv=2, D=16, seed=23)
    S = q.shape[1]
    P_sp = 4
    plan = pctx.plan(
        AttnShapes(B=2, Sq=S, Hq=4, Hkv=2, D=16, dtype_bytes=4),
        causal=True, topology=two_pods(2),
    )
    assert plan.strategy == "tokenring2d", plan.strategy
    assert plan.topology_decision["chosen"] == "tokenring2d"
    ref, _ = attention_reference(q, k, v, causal=True)
    qz, kz, vz = (to_zigzag(x, P_sp, axis=1) for x in (q, k, v))
    # plan() is called directly (sp_attention has no topology hook yet), so
    # positions must already be per-batch rows
    pos = jnp.broadcast_to(_positions(S, P_sp, "zigzag"), (2, S))
    out = jax.jit(lambda q, k, v, p: plan(q, k, v, p, p))(qz, kz, vz, pos)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(to_zigzag(ref, P_sp, axis=1)), **TOL
    )
    print("PASS hybrid tokenring2d via plan(topology=two_pods) (2 pods x 2 sp)")


def check_decode():
    mesh = make_mesh((2, 4), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), impl="xla", block_k=32)
    B, Skv, Hq, Hkv, D = 2, 256, 8, 2, 32
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Skv, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Skv, Hkv, D)), jnp.float32)
    # only first `filled` slots are real; rest are padding sentinel
    filled = 200
    k_pos = jnp.where(
        jnp.arange(Skv) < filled, jnp.arange(Skv), PAD_POS
    ).astype(jnp.int32)
    q_pos = jnp.array([filled], jnp.int32)
    out = jax.jit(
        lambda q, kc, vc, kp, qp: sp_decode(q, kc, vc, kp, qp, pctx=pctx)
    )(q, kc, vc, k_pos, q_pos)
    ref, _ = attention_reference(
        q, kc[:, :filled], vc[:, :filled], causal=True,
        q_pos=q_pos, k_pos=jnp.arange(filled, dtype=jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    print("PASS decode (sharded cache, partial fill)")


def check_prefill_chunk():
    """Chunked SP prefill: a replicated prompt chunk against the resident
    sharded cache + its own local block, merged with Update() — equals the
    single-device oracle over the full visible prefix."""
    from repro.core import sp_prefill

    mesh = make_mesh((2, 4), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), impl="xla", block_k=32)
    B, Smax, C, Hq, Hkv, D = 2, 256, 16, 8, 2, 32
    filled = 96  # cache slots already holding previous chunks
    rng = np.random.default_rng(43)
    kc = jnp.asarray(rng.standard_normal((B, Smax, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Smax, Hkv, D)), jnp.float32)
    k_pos = jnp.where(
        jnp.arange(Smax) < filled, jnp.arange(Smax), PAD_POS
    ).astype(jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, C, Hq, D)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((B, C, Hkv, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, C, Hkv, D)), jnp.float32)
    chunk_pos = filled + jnp.arange(C, dtype=jnp.int32)

    out = jax.jit(
        lambda q, kn, vn, kc, vc: sp_prefill(
            q, kn, vn, chunk_pos, kc, vc, k_pos, chunk_pos, pctx=pctx
        )
    )(q, k_new, v_new, kc, vc)

    k_full = jnp.concatenate([kc[:, :filled], k_new], axis=1)
    v_full = jnp.concatenate([vc[:, :filled], v_new], axis=1)
    pos_full = jnp.concatenate([jnp.arange(filled, dtype=jnp.int32), chunk_pos])
    ref, _ = attention_reference(
        q, k_full, v_full, causal=True, q_pos=chunk_pos, k_pos=pos_full
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)

    # the empty-cache corner (first chunk of a fresh slot): resident partial
    # is the merge identity, the chunk's own causal block is the answer
    empty_pos = jnp.full((Smax,), PAD_POS, jnp.int32)
    first_pos = jnp.arange(C, dtype=jnp.int32)
    out0 = jax.jit(
        lambda q, kn, vn, kc, vc: sp_prefill(
            q, kn, vn, first_pos, kc, vc, empty_pos, first_pos, pctx=pctx
        )
    )(q, k_new, v_new, kc, vc)
    ref0, _ = attention_reference(
        q, k_new, v_new, causal=True, q_pos=first_pos, k_pos=first_pos
    )
    np.testing.assert_allclose(np.asarray(out0), np.asarray(ref0), **TOL)
    print("PASS prefill chunk (resident sharded cache + Update() merge)")


def check_paged():
    """Paged serving steps on a real mesh: the page pool's page dimension
    shards over the SP axis (pages stripe across the ring, so a block table
    wider than one device's page budget spans devices), the gathered view
    re-enters the same sp_prefill/sp_decode partial-merge path, and the
    result equals the single-device dense chain."""
    from repro.configs import ARCHS
    from repro.models import build_model
    from repro.serving.kv_cache import PageAllocator, pages_for

    cfg = ARCHS["qwen3-1.7b"].reduced(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16, d_ff=128,
        vocab_size=97, dtype="float32", param_dtype="float32",
    )
    prompt = list(np.random.default_rng(29).integers(1, 90, 24))
    n_decode = 3
    ps, W, n_pages = 4, 16, 32  # 32 pages / 8 devices = 4-page budget each;
    # this prompt + decode span 7 pages -> necessarily crosses devices

    # single-device dense oracle
    d_pctx = ParallelContext(mesh=None, impl="xla")
    d_bundle = build_model(cfg, d_pctx)
    params = d_bundle.init(jax.random.PRNGKey(0))
    cache = d_bundle.init_serve_state(1, 64)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    pos = jnp.arange(len(prompt), dtype=jnp.int32)[None, :]
    ref_logits, _ = jax.jit(d_bundle.prefill)(params, toks, pos, cache)
    ref_logits.block_until_ready()
    ref = [np.asarray(ref_logits[0])]
    dstep = jax.jit(lambda p, t, s: d_bundle.decode_step(p, t, s))
    dcache = jax.jit(d_bundle.prefill)(params, toks, pos, cache)[1]
    tok = int(np.argmax(ref[0]))
    for _ in range(n_decode):
        l, dcache = dstep(params, jnp.asarray([tok], jnp.int32), dcache)
        l.block_until_ready()
        ref.append(np.asarray(l[0]))
        tok = int(np.argmax(ref[-1]))

    # paged chain on the (data=2, model=4) mesh — once through the gather
    # oracle (impl=xla) and once through the fused paged-decode kernel in
    # interpreter mode (impl=pallas_interpret): each shard runs the kernel
    # over its contiguous pool stripe via the remapped block table, merged
    # by the same psum lse-merge.
    mesh = make_mesh((2, 4), ("data", "model"))

    def run_chain(impl):
        pctx = ParallelContext(
            mesh=mesh, sp_axes=("model",), impl=impl, block_k=8
        )
        bundle = build_model(cfg, pctx)
        state = bundle.init_paged_state(n_pages, ps, 2, W)
        alloc = PageAllocator(n_pages)
        bt = np.full((2, W), n_pages, np.int32)
        pages = alloc.alloc(pages_for(len(prompt) + n_decode, ps))[::-1]
        bt[0, : len(pages)] = pages
        state = dict(state, block_tables=jnp.asarray(bt))
        cstep = jax.jit(bundle.prefill_chunk_paged)
        filled, chunk, logits = 0, 8, None
        while filled < len(prompt):
            a = min(chunk, len(prompt) - filled)
            t = np.zeros((2, chunk), np.int32)
            t[0, :a] = prompt[filled:filled + a]
            nv = np.zeros((2,), np.int32)
            nv[0] = a
            logits, state = cstep(params, jnp.asarray(t), state, jnp.asarray(nv))
            logits.block_until_ready()
            filled += a
        outs = [np.asarray(logits[0])]
        pstep = jax.jit(lambda p, t, s: bundle.decode_step_paged(p, t, s))
        tok = int(np.argmax(ref[0]))  # teacher-forced on the dense oracle
        for i in range(n_decode):
            l, state = pstep(params, jnp.asarray([tok, 0], jnp.int32), state)
            l.block_until_ready()
            outs.append(np.asarray(l[0]))
            tok = int(np.argmax(ref[i + 1]))
        return outs

    gather = run_chain("xla")
    for got, want in zip(gather, ref):
        np.testing.assert_allclose(got, want, **TOL)
    print("PASS paged (SP-sharded page pool == single-device dense chain)")

    fused = run_chain("pallas_interpret")
    for i, (got, want) in enumerate(zip(fused, ref)):
        np.testing.assert_allclose(got, want, **TOL)
        assert int(np.argmax(fused[i])) == int(np.argmax(gather[i])), (
            f"step {i}: fused kernel and gather oracle pick different tokens"
        )
    print(
        "PASS paged fused kernel (interpret-mode paged decode on 8 shards "
        "token-identical with the gather oracle)"
    )


def check_scan():
    mesh = make_mesh((2, 4), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), layout="contig")
    B, S, Dst = 2, 64, 8
    rng = np.random.default_rng(17)
    a = jnp.asarray(rng.uniform(0.5, 0.99, (B, S, Dst)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, S, Dst)), jnp.float32)
    h = jax.jit(lambda a, b: sp_scan(a, b, pctx=pctx))(a, b)
    # oracle: sequential scan
    href = np.zeros((B, Dst), np.float32)
    outs = []
    an, bn = np.asarray(a), np.asarray(b)
    for t in range(S):
        href = an[:, t] * href + bn[:, t]
        outs.append(href.copy())
    ref = np.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(h), ref, atol=1e-5, rtol=1e-5)
    print("PASS sp_scan (8-way chunked recurrence)")


def check_scan_hybrid():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("pod", "model"), layout="contig")
    B, S, Dst = 2, 32, 4
    rng = np.random.default_rng(19)
    a = jnp.asarray(rng.uniform(0.5, 0.99, (B, S, Dst)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, S, Dst)), jnp.float32)
    h = jax.jit(lambda a, b: sp_scan(a, b, pctx=pctx))(a, b)
    href = np.zeros((B, Dst), np.float32)
    outs = []
    an, bn = np.asarray(a), np.asarray(b)
    for t in range(S):
        href = an[:, t] * href + bn[:, t]
        outs.append(href.copy())
    np.testing.assert_allclose(np.asarray(h), np.stack(outs, 1), atol=1e-5, rtol=1e-5)
    print("PASS sp_scan multi-pod (pod x model chunked recurrence)")


def check_moe():
    """a2a expert-parallel dispatch == the single-device dropless layer
    (fwd + grad), at a capacity that drops nothing."""
    from repro.models.config import ArchConfig
    from repro.models.moe import moe_init, moe_ffn

    cfg = ArchConfig(
        name="moe-check", family="moe", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab_size=64, n_experts=8,
        n_experts_per_token=2, moe_d_ff=64, capacity_factor=4.0,  # no drops
        dtype="float32", param_dtype="float32",
    )
    p = moe_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(23)
    B, S = 4, 32
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)), jnp.float32)

    dense_pctx = ParallelContext(mesh=None)
    y_ref, aux_ref, _ = jax.jit(lambda p, x: moe_ffn(p, x, cfg, dense_pctx))(p, x)

    mesh = make_mesh((2, 4), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), impl="xla")
    y, aux, _ = jax.jit(lambda p, x: moe_ffn(p, x, cfg, pctx))(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(float(aux), float(aux_ref), atol=1e-4, rtol=1e-4)

    w = jnp.asarray(rng.standard_normal(y_ref.shape), jnp.float32)

    def loss_a2a(p, x):
        y, aux, _ = moe_ffn(p, x, cfg, pctx)
        return jnp.sum(y * w) + aux

    def loss_dense(p, x):
        y, aux, _ = moe_ffn(p, x, cfg, dense_pctx)
        return jnp.sum(y * w) + aux

    g1 = jax.jit(jax.grad(loss_a2a))(p, x)
    g2 = jax.jit(jax.grad(loss_dense))(p, x)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(g1)[0],
        jax.tree_util.tree_flatten_with_path(g2)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4,
            err_msg=str(path),
        )
    print("PASS moe a2a dispatch (fwd + grads vs dense oracle)")


def check_sharded_ce():
    """Vocab-parallel (constrained) CE on a mesh == single-device CE."""
    from repro.models.layers import chunked_cross_entropy

    rng = np.random.default_rng(29)
    B, S, d, V = 4, 64, 32, 96
    x = jnp.asarray(rng.standard_normal((B, S, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, V)) * 0.05, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)

    ref, refn = jax.jit(
        lambda x, w: chunked_cross_entropy(
            x, w, labels, mask=mask, chunk=16, compute_dtype=jnp.float32
        )
    )(x, w)

    mesh = make_mesh((2, 4), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), impl="xla")
    got, gotn = jax.jit(
        lambda x, w: chunked_cross_entropy(
            x, w, labels, mask=mask, pctx=pctx, compute_dtype=jnp.float32,
            chunk=16,
        )
    )(x, w)
    np.testing.assert_allclose(float(got), float(ref), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(gotn), float(refn))

    g_ref = jax.jit(
        jax.grad(
            lambda x, w: chunked_cross_entropy(
                x, w, labels, mask=mask, chunk=16, compute_dtype=jnp.float32
            )[0],
            argnums=(0, 1),
        )
    )(x, w)
    g = jax.jit(
        jax.grad(
            lambda x, w: chunked_cross_entropy(
                x, w, labels, mask=mask, pctx=pctx, compute_dtype=jnp.float32,
                chunk=16,
            )[0],
            argnums=(0, 1),
        )
    )(x, w)
    for a, b, nm in zip(g, g_ref, ["dx", "dw"]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5, err_msg=nm
        )
    print("PASS sharded vocab-parallel CE (fwd + grads)")


def check_travel_dtype():
    """TokenRing with bf16 accumulator wire: same result within bf16 tol."""
    mesh = make_mesh((2, 4), ("data", "model"))
    q, k, v = _data(Hq=4, Hkv=4, seed=31)
    S = q.shape[1]
    ref, _ = attention_reference(q, k, v, causal=True)
    qz, kz, vz = (to_zigzag(x, 4, axis=1) for x in (q, k, v))
    pos = _positions(S, 4, "zigzag")
    pctx = ParallelContext(
        mesh=mesh, sp_axes=("model",), strategy="tokenring", impl="xla",
        block_q=64, block_k=64, travel_dtype="bfloat16",
    )
    out = jax.jit(
        lambda q, k, v, p: sp_attention(q, k, v, p, p, pctx=pctx, causal=True)
    )(qz, kz, vz, pos)
    err = np.max(np.abs(np.asarray(out, np.float32) - np.asarray(to_zigzag(ref, 4, axis=1))))
    assert err < 5e-2, err  # bf16 merge rounding, ~P accumulations
    print(f"PASS tokenring travel_dtype=bf16 (max err {err:.2e} < 5e-2)")


def check_window():
    """Halo-exchange window strategy == windowed single-device oracle, and
    the planner routes windowed layers to it from any configured strategy."""
    from repro.core.api import AttnShapes

    mesh = make_mesh((2, 4), ("data", "model"))
    B, S, Hq, Hkv, D, W = 2, 256, 4, 2, 32, 96
    rng = np.random.default_rng(37)
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    ref, _ = attention_reference(q, k, v, causal=True, window=W)
    pos = jnp.arange(S, dtype=jnp.int32)
    for strategy in ["tokenring", "auto"]:
        pctx = ParallelContext(
            mesh=mesh, sp_axes=("model",), strategy=strategy, layout="contig",
            impl="xla", block_q=64, block_k=64,
        )
        plan = pctx.plan(
            AttnShapes(B=B, Sq=S, Hq=Hq, Hkv=Hkv, D=D, dtype_bytes=4),
            causal=True, window=W,
        )
        assert plan.strategy == "window", plan.strategy
        assert plan.cost.fwd_bytes > 0 and plan.cost.bwd_bytes == 0
        out = jax.jit(
            lambda q, k, v, p: sp_attention(
                q, k, v, p, p, pctx=pctx, causal=True, window=W
            )
        )(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
        print(f"PASS window halo-exchange (planned from strategy={strategy})")


def check_overlap():
    """The tentpole's three guarantees, pinned on real compiled HLO:

    1. pipelined (overlap=True) and sequential (overlap=False) executions of
       the same schedule are bitwise identical — the executor only moves
       dependency edges, never data;
    2. the scan body of a pipelined schedule has NO collective-permute
       downstream of a same-step dot, while the sequential reference blocks
       every body permute (and for the fully unrolled faithful schedule,
       pipelining strictly reduces the blocked count);
    3. per-direction collective bytes are unchanged by pipelining and match
       the registered comm_cost closed form (token_ring bidir: balanced
       directions, going-home hop included).
    """
    from repro.core.strategies import strategy_cost, get_strategy
    from repro.launch.hlo_analysis import analyze_hlo, overlap_report

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    B, S, Hq, Hkv, D = 2, 256, 4, 4, 32
    q, k, v = _data(B=B, S=S, Hq=Hq, Hkv=Hkv, seed=53)
    qz, kz, vz = (to_zigzag(x, 4, axis=1) for x in (q, k, v))
    pos = _positions(S, 4, "zigzag")

    for strategy in ["tokenring", "tokenring_faithful", "ring", "ring_bidir"]:
        outs, hlos, bytes_ = {}, {}, {}
        for overlap in (True, False):
            pctx = ParallelContext(
                mesh=mesh, sp_axes=("model",), strategy=strategy,
                impl="xla", block_q=64, block_k=64, overlap=overlap,
            )
            fn = jax.jit(
                lambda q, k, v, p, pctx=pctx: sp_attention(
                    q, k, v, p, p, pctx=pctx, causal=True
                )
            )
            compiled = fn.lower(qz, kz, vz, pos).compile()  # AOT: one compile
            outs[overlap] = np.asarray(compiled(qz, kz, vz, pos))
            hlos[overlap] = compiled.as_text()
            st = analyze_hlo(hlos[overlap], world=n_dev)
            bytes_[overlap] = (st.link_bytes_fwd, st.link_bytes_bwd)

        # (1) pipelining moves edges, not data
        assert np.array_equal(outs[True], outs[False]), (
            strategy,
            np.abs(outs[True] - outs[False]).max(),
        )
        # (2) dependency structure
        rep_p = overlap_report(hlos[True])
        rep_s = overlap_report(hlos[False])
        body_p, body_s = rep_p["scan_body_total"], rep_s["scan_body_total"]
        if strategy == "tokenring_faithful":  # fully unrolled, no scan body
            assert body_p["permutes"] == 0, body_p
            assert (
                rep_p["total"]["compute_blocked"]
                < rep_s["total"]["compute_blocked"]
                == rep_s["total"]["permutes"]
            ), (rep_p["total"], rep_s["total"])
        else:
            assert body_p["permutes"] > 0 and body_p["compute_blocked"] == 0, (
                strategy, body_p,
            )
            assert body_s["compute_blocked"] == body_s["permutes"] > 0, (
                strategy, body_s,
            )
        # (3) identical per-direction bytes, matching the cost model
        assert bytes_[True] == bytes_[False], (strategy, bytes_)
        cost = strategy_cost(
            get_strategy(strategy), B // (n_dev // 4), S, Hq, Hkv, D, 4,
            bytes_per_elem=4,
        )
        fwd, bwd = bytes_[True]
        # measured includes int32 position rows the model doesn't charge;
        # the faithful variant's model charges torus hop distance while XLA
        # routes the short way (DESIGN.md §2 convention note).
        if strategy != "tokenring_faithful":
            for got, want in ((fwd, cost.fwd_bytes), (bwd, cost.bwd_bytes)):
                assert abs(got - want) <= 0.05 * max(want, 1.0), (
                    strategy, (fwd, bwd), (cost.fwd_bytes, cost.bwd_bytes),
                )
        print(
            f"PASS overlap strategy={strategy} body_blocked "
            f"{body_p['compute_blocked']}/{body_p['permutes']} pipelined vs "
            f"{body_s['compute_blocked']}/{body_s['permutes']} sequential, "
            f"dir bytes ({fwd:.0f}, {bwd:.0f}) ({n_dev} devices)"
        )


def check_analyze():
    """The static analyzer's three contracts, cross-validated on this host:

    1. the full ``repro.launch.analyze`` pass is clean over every registered
       strategy and the shape grid (the CI gate's exact code path);
    2. the symbolic byte audit (positions included) equals the per-direction
       bytes ``analyze_hlo`` measures on real compiled HLO — *exactly*, for
       every spec'd strategy at P=4 and P=8;
    3. the jaxpr-level overlap pre-check agrees with the compiled-HLO
       ``overlap_report`` verdict for pipelined vs sequential execution.
    """
    from repro.analysis.comm_audit import AuditDims, audit_schedule
    from repro.analysis.overlap_jaxpr import jaxpr_overlap_report, trace_strategy
    from repro.core.strategies import get_strategy
    from repro.launch.analyze import run_analysis
    from repro.launch.hlo_analysis import analyze_hlo, overlap_report

    # (1) the CI gate itself
    report = run_analysis()
    assert report.ok, report.render()
    print(
        f"PASS analyze static gate "
        f"({sum(report.checked.values())} sites, 0 findings)"
    )

    # (2) exact audit == HLO bytes
    n_dev = len(jax.devices())
    B, S, Hq, Hkv, D, W = 2, 256, 4, 4, 32, 96
    q, k, v = _data(B=B, S=S, Hq=Hq, Hkv=Hkv, seed=71)
    for P_sp in (4, n_dev):
        mesh = make_mesh((n_dev // P_sp, P_sp), ("data", "model"))
        B_loc = B // (n_dev // P_sp)
        for strategy in ("tokenring", "ring", "ring_bidir", "window"):
            layout = "contig" if strategy == "window" else "zigzag"
            window = W if strategy == "window" else None
            pctx = ParallelContext(
                mesh=mesh, sp_axes=("model",), strategy=strategy,
                layout=layout, impl="xla", block_q=64, block_k=64,
            )
            qx, kx, vx = (_layout(x, P_sp, layout) for x in (q, k, v))
            pos = _positions(S, P_sp, layout)
            fn = jax.jit(
                lambda q, k, v, p, pctx=pctx, window=window: sp_attention(
                    q, k, v, p, p, pctx=pctx, causal=True, window=window
                )
            )
            hlo = fn.lower(qx, kx, vx, pos).compile().as_text()
            st = analyze_hlo(hlo, world=n_dev)
            desc = get_strategy(strategy)
            spec = desc.schedule_spec(P_sp, S_loc=S // P_sp, window=window)
            dims = AuditDims(
                B=B_loc, S_loc=S // P_sp, Hq=Hq, Hkv=Hkv, D=D,
                bytes_per_elem=4, travel_bytes=4,
            )
            fwd, bwd, findings = audit_schedule(
                spec, P_sp, dims, include_positions=True, subject=strategy
            )
            assert not findings, findings
            assert (fwd, bwd) == (st.link_bytes_fwd, st.link_bytes_bwd), (
                strategy, P_sp, (fwd, bwd),
                (st.link_bytes_fwd, st.link_bytes_bwd),
            )
            print(
                f"PASS analyze bytes {strategy} P={P_sp}: audit == HLO "
                f"({fwd}, {bwd})"
            )

    # (2b) the hierarchical 2D schedule: three *independent* derivations of
    # its wire bytes — the symbolic hop audit, the compiled HLO's measured
    # collective shapes, and the per-link topology ledger summed over lanes
    # — must agree exactly (ISSUE: planner choice certified by the prover).
    if n_dev % 2 == 0 and n_dev >= 4:
        from repro.analysis.topo_check import build_ledger
        from repro.core.api import AttnShapes
        from repro.core.topology import two_pods

        n_pods, n_inner = 2, n_dev // 2
        mesh2d = make_mesh((n_pods, n_inner), ("pod", "model"))
        topo = two_pods(n_inner)
        pctx = ParallelContext(
            mesh=mesh2d, data_axis=None, sp_axes=("pod", "model"),
            strategy="tokenring2d", impl="xla", block_q=32, block_k=32,
        )
        plan = pctx.plan(
            AttnShapes(B=B, Sq=S, Hq=Hq, Hkv=Hkv, D=D, dtype_bytes=4),
            causal=True, topology=topo,
        )
        assert plan.strategy == "tokenring2d"
        qz, kz, vz = (to_zigzag(x, n_dev, axis=1) for x in (q, k, v))
        pos = jnp.broadcast_to(_positions(S, n_dev, "zigzag"), (B, S))
        fn = jax.jit(lambda q, k, v, p: plan(q, k, v, p, p))
        hlo = fn.lower(qz, kz, vz, pos).compile().as_text()
        st = analyze_hlo(hlo, world=n_dev)
        desc = get_strategy("tokenring2d")
        spec = desc.schedule_spec(n_dev, S_loc=S // n_dev, n_pods=n_pods)
        dims = AuditDims(
            B=B, S_loc=S // n_dev, Hq=Hq, Hkv=Hkv, D=D,
            bytes_per_elem=4, travel_bytes=4,
        )
        fwd, bwd, findings = audit_schedule(
            spec, n_dev, dims, include_positions=True, subject="tokenring2d"
        )
        assert not findings, findings
        assert (fwd, bwd) == (st.link_bytes_fwd, st.link_bytes_bwd), (
            (fwd, bwd), (st.link_bytes_fwd, st.link_bytes_bwd),
        )
        # ledger lanes carry all P ranks' messages; grid placement maps every
        # logical hop onto exactly one wire, so lane sums are P x per-rank
        dirs = build_ledger(
            spec, dims, topo, placement="grid", include_positions=True
        ).lane_dir_totals()
        led = (
            sum(d["fwd"] for d in dirs.values()) // n_dev,
            sum(d["bwd"] for d in dirs.values()) // n_dev,
        )
        assert led == (fwd, bwd), (led, (fwd, bwd))
        print(
            f"PASS analyze bytes tokenring2d P={n_dev}: audit == HLO == "
            f"link ledger ({fwd}, {bwd})"
        )

    # (3) jaxpr overlap pre-check == compiled-HLO verdict
    mesh4 = make_mesh((n_dev // 4, 4), ("data", "model"))
    qz, kz, vz = (to_zigzag(x, 4, axis=1) for x in (q, k, v))
    pos = _positions(S, 4, "zigzag")
    for strategy in ("tokenring", "ring", "ring_bidir"):
        desc = get_strategy(strategy)
        for overlap in (True, False):
            jrep = jaxpr_overlap_report(
                trace_strategy(desc, P=4, overlap=overlap)
            )["scan_body_total"]
            pctx = ParallelContext(
                mesh=mesh4, sp_axes=("model",), strategy=strategy,
                impl="xla", block_q=64, block_k=64, overlap=overlap,
            )
            fn = jax.jit(
                lambda q, k, v, p, pctx=pctx: sp_attention(
                    q, k, v, p, p, pctx=pctx, causal=True
                )
            )
            hrep = overlap_report(
                fn.lower(qz, kz, vz, pos).compile().as_text()
            )["scan_body_total"]
            assert (jrep["blocked"] == 0) == (hrep["compute_blocked"] == 0), (
                strategy, overlap, jrep, hrep,
            )
            if not overlap:  # sequential mode blocks every body permute
                assert jrep["blocked"] == jrep["permutes"] > 0, (
                    strategy, jrep,
                )
        print(f"PASS analyze overlap pre-check agrees with HLO ({strategy})")


def check_registry_plugin():
    """A strategy registered from *outside* core runs through sp_attention
    with no edits to the API — the registry's extensibility contract."""
    from repro.core.merge import finalize
    from repro.core.strategies import (
        CommCost,
        register_strategy,
        unregister_strategy,
    )
    from repro.kernels.ops import flash_attention

    def allgather_sp(
        q, k, v, q_pos, k_pos, *, axis_name, causal=False, window=None,
        scale=None, impl="auto", block_q=512, block_k=512, block_q_bwd=None,
        block_k_bwd=None, overlap=True, return_lse=False,
    ):
        # Naive baseline: gather every KV shard and attend locally.
        k_all = jax.lax.all_gather(k, axis_name, axis=1, tiled=True)
        v_all = jax.lax.all_gather(v, axis_name, axis=1, tiled=True)
        kp_all = jax.lax.all_gather(k_pos, axis_name, axis=1, tiled=True)
        out, lse = flash_attention(
            q, k_all, v_all, q_pos=q_pos, k_pos=kp_all, causal=causal,
            window=window, scale=scale, impl=impl, block_q=block_q,
            block_k=block_k,
        )
        out, lse = finalize(out, lse)
        return (out, lse) if return_lse else out

    def allgather_cost(B, S, Hq, Hkv, D, P, *, bytes_per_elem=2, **_):
        # bidirectional ring all-gather: (P-1)/P of the KV bytes, half each way
        kv = 2 * B * (S // P) * Hkv * D * bytes_per_elem * (P - 1)
        return CommCost(kv / 2, kv / 2)

    register_strategy(
        "toy_allgather", allgather_sp, comm_cost=allgather_cost,
        auto_eligible=False,
        description="toy plugin: all-gather KV, attend locally",
    )
    try:
        mesh = make_mesh((2, 4), ("data", "model"))
        pctx = ParallelContext(
            mesh=mesh, sp_axes=("model",), strategy="toy_allgather",
            impl="xla", block_q=64, block_k=64,
        )
        q, k, v = _data(Hq=8, Hkv=2, seed=41)
        S = q.shape[1]
        ref, _ = attention_reference(q, k, v, causal=True)
        qz, kz, vz = (_layout(x, 4, "zigzag") for x in (q, k, v))
        pos = _positions(S, 4, "zigzag")
        out = jax.jit(
            lambda q, k, v, p: sp_attention(q, k, v, p, p, pctx=pctx, causal=True)
        )(qz, kz, vz, pos)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(to_zigzag(ref, 4, axis=1)), **TOL
        )
    finally:
        unregister_strategy("toy_allgather")
    print("PASS registry plugin (toy strategy through sp_attention)")


def check_prefix():
    """The adaptive-prefill tentpole on a real mesh, two halves:

    1. warm-cache serving — a mesh-built engine with the content-addressed
       prefix cache serves a repeated prompt (full hit) and a mid-page fork
       (one COW copy) emitting exactly the tokens of the cold no-cache
       engine, with zero prefill tokens spent on the fully resident prompt;
    2. prefill-ring byte audit — for ``passkv_ring`` and ``passq_ring`` at
       P=4 and P=<device count>, the symbolic schedule audit (positions
       included) equals the per-direction bytes measured on compiled HLO,
       and the positions-free audit equals the registered ``comm_cost``
       closed form exactly (``audit_strategy`` returns no findings).
    """
    from repro.analysis.comm_audit import (
        AuditDims,
        audit_schedule,
        audit_strategy,
    )
    from repro.configs import ARCHS
    from repro.core.strategies import get_strategy
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.models import build_model
    from repro.serving.engine import ServingEngine

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    cfg = ARCHS["qwen3-1.7b"].reduced(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16, d_ff=128,
        vocab_size=97, dtype="float32", param_dtype="float32",
    )
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), impl="xla", block_k=8)
    bundle = build_model(cfg, pctx)
    params = bundle.init(jax.random.PRNGKey(0))
    prompt = list(np.random.default_rng(61).integers(1, 90, 25))
    fork = prompt[:20] + [(t + 3) % 90 + 1 for t in prompt[20:]]

    def engine(prefix_cache):
        return ServingEngine(
            bundle, params, max_batch=2, max_len=64, prefill_chunk=8,
            page_size=8, max_pages=32, prefix_cache=prefix_cache,
        )

    cold_eng = engine(False)
    cold = cold_eng.submit(prompt, max_new_tokens=4)
    cold_fork = cold_eng.submit(fork, max_new_tokens=4)
    cold_eng.run()

    eng = engine(True)
    first = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    pt_cold = eng.counters["prefill_tokens"]
    warm = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    assert eng.counters["prefill_tokens"] == pt_cold, (
        "fully resident prompt must not re-prefill"
    )
    forked = eng.submit(fork, max_new_tokens=4)
    eng.run()
    s = eng.stats()["prefix"]
    assert first.output == warm.output == cold.output, (
        first.output, warm.output, cold.output,
    )
    assert forked.output == cold_fork.output, (forked.output, cold_fork.output)
    assert s["cow_copies"] == 1 and s["hit_tokens"] >= 40, s
    print(
        f"PASS prefix warm serving == cold engine "
        f"(hit rate {s['hit_rate']:.2f}, 1 COW, {n_dev} devices)"
    )

    B, S, Hq, Hkv, D = 2, 256, 4, 4, 32
    q, k, v = _data(B=B, S=S, Hq=Hq, Hkv=Hkv, seed=67)
    for P_sp in (4, n_dev):
        mesh_p = make_mesh((n_dev // P_sp, P_sp), ("data", "model"))
        B_loc = B // (n_dev // P_sp)
        for strategy in ("passkv_ring", "passq_ring"):
            pctx_p = ParallelContext(
                mesh=mesh_p, sp_axes=("model",), strategy=strategy,
                impl="xla", block_q=64, block_k=64,
            )
            qz, kz, vz = (to_zigzag(x, P_sp, axis=1) for x in (q, k, v))
            pos = _positions(S, P_sp, "zigzag")
            fn = jax.jit(
                lambda q, k, v, p, pctx=pctx_p: sp_attention(
                    q, k, v, p, p, pctx=pctx, causal=True
                )
            )
            hlo = fn.lower(qz, kz, vz, pos).compile().as_text()
            st = analyze_hlo(hlo, world=n_dev)
            desc = get_strategy(strategy)
            spec = desc.schedule_spec(P_sp, S_loc=S // P_sp, window=None)
            dims = AuditDims(
                B=B_loc, S_loc=S // P_sp, Hq=Hq, Hkv=Hkv, D=D,
                bytes_per_elem=4, travel_bytes=4,
            )
            fwd, bwd, findings = audit_schedule(
                spec, P_sp, dims, include_positions=True, subject=strategy
            )
            assert not findings, findings
            assert (fwd, bwd) == (st.link_bytes_fwd, st.link_bytes_bwd), (
                strategy, P_sp, (fwd, bwd),
                (st.link_bytes_fwd, st.link_bytes_bwd),
            )
            assert audit_strategy(
                desc, B=B_loc, S=S, Hq=Hq, Hkv=Hkv, D=D, P=P_sp,
                bytes_per_elem=4, travel_dtype="float32",
            ) == []
            print(
                f"PASS prefix ring bytes {strategy} P={P_sp}: "
                f"audit == HLO == comm_cost ({fwd}, {bwd})"
            )


def check_resilience():
    """The resilience runtime on a real mesh, two halves:

    1. chaos serving — a mesh-built engine with one scheduled fault at
       every tick-point class (admit, alloc, prefill_tick, decode_once,
       sample) plus periodic cache audits recovers through quarantine/
       retry and emits exactly the fault-free engine's tokens;
    2. snapshot restart — an engine killed mid-flight restarts from its
       serving-state snapshot (``ServingEngine.from_snapshot``) with a
       clean audit and completes token-exact vs the same oracle.
    """
    import tempfile

    from repro.configs import ARCHS
    from repro.models import build_model
    from repro.serving.engine import ServingEngine
    from repro.serving.resilience import FaultPlan, FaultSpec

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    cfg = ARCHS["qwen3-1.7b"].reduced(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16, d_ff=128,
        vocab_size=97, dtype="float32", param_dtype="float32",
    )
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), impl="xla", block_k=8)
    bundle = build_model(cfg, pctx)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(71)
    prompts = [list(rng.integers(1, 90, n)) for n in (12, 9, 15)]

    def engine(**kw):
        return ServingEngine(
            bundle, params, max_batch=2, max_len=64, prefill_chunk=8,
            page_size=8, max_pages=32, prefix_cache=True,
            max_retries=5, retry_backoff=1, **kw,
        )

    oracle_eng = engine()
    oracle = [oracle_eng.submit(p, max_new_tokens=4) for p in prompts]
    oracle_eng.run()

    plan = FaultPlan([
        FaultSpec("admit", nth=1),
        FaultSpec("alloc", nth=1),
        FaultSpec("prefill_tick", nth=1),
        FaultSpec("decode_once", nth=2),
        FaultSpec("sample", nth=3),
    ])
    eng = engine(fault_plan=plan, audit_every=2)
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run()
    assert len(plan.fired) == 5, plan.fired
    assert all(r.status == "done" for r in reqs), [r.status for r in reqs]
    assert [r.output for r in reqs] == [o.output for o in oracle], (
        [r.output for r in reqs], [o.output for o in oracle],
    )
    eng.auditor.check()
    assert eng.counters["recoveries"] >= 1, eng.counters
    assert eng.counters["quarantines"] >= 1, eng.counters
    print(
        f"PASS resilience chaos: 5 injected faults across all tick-point "
        f"classes, outputs == fault-free oracle ({n_dev} devices)"
    )

    with tempfile.TemporaryDirectory() as snapdir:
        eng = engine(snapshot_dir=snapdir)
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        eng.run(max_steps=3)
        step = eng.snapshot()
        del eng  # the "killed" process
        eng2 = ServingEngine.from_snapshot(bundle, params, snapdir, step=step)
        eng2.auditor.check()
        eng2.run()
        outs = {r.uid: r.output for r in eng2.done}
        assert [outs[o.uid] for o in oracle] == [o.output for o in oracle], (
            outs, [o.output for o in oracle],
        )
    print(
        f"PASS resilience restart: snapshot step {step} resumed token-exact "
        f"on a fresh engine ({n_dev} devices)"
    )


def check_ring_scopes():
    """A TokenRing step (zigzag, pipelined, forward + vjp) names its parts:
    the compiled HLO's op paths carry ``ring_send``, ``ring_compute`` and
    ``ring_merge``, in the forward's ops and in the backward's (under
    ``transpose(jvp())``), and the scopes are metadata only: with them
    turned off the outputs are bitwise the same."""
    import re
    import contextlib
    from unittest import mock

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    q, k, v = _data(B=1, S=256, Hq=4, Hkv=2, D=32, seed=7)
    qz, kz, vz = (to_zigzag(x, 4, axis=1) for x in (q, k, v))
    pos = _positions(256, 4, "zigzag")[None]
    pctx = ParallelContext(
        mesh=mesh, sp_axes=("model",), data_axis="data", strategy="tokenring",
        layout="zigzag", impl="xla", block_q=64, block_k=64, overlap=True,
    )

    def build():
        def sp_step(q, k, v, g):
            out, vjp = jax.vjp(
                lambda q, k, v: sp_attention(q, k, v, pos, pos, pctx=pctx, causal=True),
                q, k, v,
            )
            return (out, *vjp(g))

        return jax.jit(sp_step).lower(qz, kz, vz, qz).compile()

    scoped = build()
    with mock.patch.object(jax, "named_scope", lambda name: contextlib.nullcontext()):
        plain = build()
    paths = re.findall(r'op_name="([^"]+)"', scoped.as_text())
    for scope in ("ring_send", "ring_compute", "ring_merge"):
        for part in ("jit(sp_step)/jvp()", "jit(sp_step)/transpose(jvp())"):
            assert any(p.startswith(part) and f"/{scope}" in p for p in paths), (
                f"no {scope} among the op paths under {part}"
            )
    assert "ring_merge" not in plain.as_text()
    for a, b in zip(scoped(qz, kz, vz, qz), plain(qz, kz, vz, qz)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    print(f"PASS ring scopes: send/compute/merge named, outputs bitwise unchanged "
          f"({n_dev} devices)")


CHECKS = {
    "strategies": check_strategies,
    "overlap": check_overlap,
    "window": check_window,
    "registry": check_registry_plugin,
    "analyze": check_analyze,
    "gradients": check_gradients,
    "hybrid": check_hybrid,
    "decode": check_decode,
    "prefill": check_prefill_chunk,
    "paged": check_paged,
    "prefix": check_prefix,
    "resilience": check_resilience,
    "scan": check_scan,
    "scan_hybrid": check_scan_hybrid,
    "moe": check_moe,
    "sharded_ce": check_sharded_ce,
    "travel": check_travel_dtype,
    "scopes": check_ring_scopes,
}


def main(argv):
    names = argv[1:] or list(CHECKS)
    want = int(os.environ.get("REPRO_CHECK_DEVICES", "8"))
    assert len(jax.devices()) >= want, jax.devices()
    for name in names:
        CHECKS[name]()
    print("ALL CHECKS PASSED")


if __name__ == "__main__":
    main(sys.argv)
