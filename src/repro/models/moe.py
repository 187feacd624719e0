"""Mixture-of-Experts FFN (qwen3-moe 128e top-8, llama4-scout 16e top-1).

Routing is the published one in float32: logits over every expert, top-k,
gates renormalized over the k picked (qwen "norm_topk_prob").

  * single device (``pctx`` inactive): **dropless** grouped dispatch.  The
    (token, k) entries of valid tokens are sorted by expert, the experts run
    as grouped matmuls over their contiguous groups (``jax.lax.ragged_dot``),
    and the outputs are unsorted and gate-weighted.  No capacity, no drops;
    masked tokens (padding rows of a prefill chunk, inactive decode slots)
    get no entries and output zero.  Shapes stay static: masked entries sort
    to a tail that no group covers.
  * distributed (``pctx`` active): GShard/Switch capacity dispatch over the
    (data, model) mesh with an all-to-all each way (``_moe_ffn_a2a``), or
    replicated tokens and a psum for an unshardable sequence (decode).
    Entries beyond capacity C = ceil(S*K/E * capacity_factor) are dropped.

The Switch load-balancing auxiliary loss is returned for the trainer.  An
optional shared expert (llama4) runs densely alongside the routed experts.

Ops carry the name scopes ``moe_route`` (router, top-k, sort),
``moe_gmm`` (the grouped matmuls and the SwiGLU between them) and
``moe_combine`` (unsort, gate-weighted sum), so a device trace can attribute
the expert layer's time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from repro.core.compat import shard_map

from repro.models.layers import dense_init, mlp, mlp_init

__all__ = ["moe_init", "moe_ffn", "moe_counters", "moe_tally"]


def moe_init(key, cfg):
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, E, dtype=cfg.param_dtype),
        "wg": (jax.random.normal(ks[1], (E, d, f), dt) * d**-0.5).astype(dt),
        "wu": (jax.random.normal(ks[2], (E, d, f), dt) * d**-0.5).astype(dt),
        "wd": (jax.random.normal(ks[3], (E, f, d), dt) * f**-0.5).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(
            ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts, mlp_type="swiglu",
            dtype=cfg.param_dtype,
        )
    return p


def moe_counters():
    """Device counters of the single-device expert layer, carried in a serve
    state: expert rows of valid tokens (``routed``), experts with at least
    one row summed over layers and steps (``touched``), and the steps that
    ran the layer (``steps``).  uint32: they wrap at 2**32, so read them as
    differences modulo 2**32."""
    return {k: jnp.zeros((), jnp.uint32) for k in ("routed", "touched", "steps")}


def moe_tally(counters, counts):
    """``counters`` after one step whose layers' counts sum to ``counts``."""
    return {
        "routed": counters["routed"] + counts["routed"].astype(jnp.uint32),
        "touched": counters["touched"] + counts["touched"].astype(jnp.uint32),
        "steps": counters["steps"] + jnp.uint32(1),
    }


def moe_ffn(p, x, cfg, pctx, valid=None):
    """x (B,S,d) -> (y (B,S,d), aux_loss scalar, counts).

    ``valid (B,S)`` (bool, optional) marks the real tokens; the others route
    to no expert and output zero (single device; the distributed paths route
    every token).  ``counts``: ``{"routed", "touched"}`` int32 scalars of the
    single-device layer (expert rows run, experts with at least one row), or
    None on the distributed paths.

    Dispatch impl:
      * distributed (pctx active): shard_map all-to-all expert parallelism —
        tokens are bucketed per destination device, exchanged with ONE
        ``lax.all_to_all`` each way, and dispatched locally.  Collective
        volume is O(tokens * K * d) — the production EP pattern.  (The naive
        pjit scatter formulation all-reduces the (B,E,C,d) capacity buffer:
        measured 17 s collective term on qwen3-moe train_4k; §Perf iter 1.)
      * single-device: dropless sort + grouped matmul (``_moe_ffn_dropless``).
    """
    if pctx is not None and pctx.active:
        if x.shape[1] % pctx.sp_degree == 0:
            return (*_moe_ffn_a2a(p, x, cfg, pctx), None)
        # seq dim not shardable (decode S=1): tokens replicated over the EP
        # axes, each rank computes only entries routed to ITS experts, psum.
        return (*_moe_ffn_replicated_seq(p, x, cfg, pctx), None)
    return _moe_ffn_dropless(p, x, cfg, valid)


def _moe_ffn_dropless(p, x, cfg, valid):
    """Single-device dropless dispatch: sort entries by expert, grouped
    matmuls over the groups, unsort and combine in float32."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.n_experts_per_token
    N = B * S
    dt = jnp.dtype(cfg.dtype)
    xf = x.reshape(N, d)
    ok = jnp.ones((N,), bool) if valid is None else valid.reshape(N)

    with jax.named_scope("moe_route"):
        logits = jnp.einsum(
            "nd,de->ne", xf.astype(jnp.float32), p["router"]["w"].astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)  # (N,E)
        gate_vals, expert_idx = jax.lax.top_k(logits, K)  # (N,K)
        gates = jax.nn.softmax(gate_vals, axis=-1)  # renormalize over the K picked
        # Entries of masked tokens take expert id E: they sort last, past
        # every group, and no matmul row belongs to them.
        flat_e = jnp.where(ok[:, None], expert_idx, E).reshape(N * K)
        order = jnp.argsort(flat_e, stable=True)
        sizes = jnp.sum(flat_e[:, None] == jnp.arange(E)[None, :], axis=0, dtype=jnp.int32)
        xs = xf.astype(dt)[order // K]  # (N*K, d), grouped by expert

    with jax.named_scope("moe_gmm"):
        g = jax.lax.ragged_dot(xs, p["wg"].astype(dt), sizes)
        u = jax.lax.ragged_dot(xs, p["wu"].astype(dt), sizes)
        ys = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["wd"].astype(dt), sizes)

    with jax.named_scope("moe_combine"):
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(N * K, dtype=order.dtype))
        y_ent = ys[inv].reshape(N, K, d).astype(jnp.float32)
        # where, not a product: rows past the last group hold no result.
        y_ent = jnp.where(ok[:, None, None], y_ent, 0.0)
        y = jnp.einsum("nkd,nk->nd", y_ent, gates).astype(dt).reshape(B, S, d)

    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x, mlp_type="swiglu", compute_dtype=dt)

    # --- Switch aux loss over valid tokens ------------------------------------
    w = ok.astype(jnp.float32)[:, None]
    n_ok = jnp.maximum(jnp.sum(w), 1.0)
    importance = jnp.sum(probs * w, axis=0) / n_ok  # (E,)
    assigned = jnp.sum(
        jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=1) * w, axis=0
    ) / n_ok  # fraction of entries routed to each expert
    aux = E * jnp.sum(importance * assigned) / K
    counts = {"routed": jnp.sum(sizes), "touched": jnp.sum(sizes > 0).astype(jnp.int32)}
    return y, aux, counts


# ---------------------------------------------------------------------------
# distributed expert parallelism: shard_map + all-to-all
# ---------------------------------------------------------------------------


def _moe_ffn_a2a(p, x, cfg, pctx):
    """Expert-parallel MoE over the SP axes.

    Inside shard_map (data: batch, model(+pod): experts):
      1. local routing (router weights replicated);
      2. bucket entries by destination device (one-hot cumsum positions,
         per-destination capacity ``C_sd``), overflow dropped;
      3. ONE ``all_to_all`` ships (token, local-expert-id, src-slot) buckets;
      4. local capacity dispatch to this device's ``E_loc`` experts, swiglu;
      5. ``all_to_all`` back, combine at source with renormalized gates.

    Capacities are static: C_sd = ceil(T_loc/P * capacity_factor),
    C_e = ceil(P*C_sd/E_loc * capacity_factor).
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P_

    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.n_experts_per_token
    dt = jnp.dtype(cfg.dtype)
    dp = pctx.data_axis
    seq = pctx.seq_spec()

    # EP axes: all SP axes when E divides them; otherwise the model axis only
    # (e.g. llama4's 16 experts on the 32-way multi-pod ring: experts are
    # replicated across pods, tokens route within their pod).
    total_sp = pctx.sp_degree
    if E % total_sp == 0:
        ep_axes = pctx.sp_axes if len(pctx.sp_axes) > 1 else pctx.sp_axes[0]
        e_entry = seq
    elif E % pctx.mesh.shape["model"] == 0:
        ep_axes = "model"
        e_entry = "model"
    else:
        raise ValueError(f"experts {E} not shardable over {pctx.sp_axes}")
    axes = ep_axes

    act = P_(dp, seq, None)
    espec = P_(e_entry, None, None)  # expert stacks sharded over the EP axes
    rspec = P_(None, None)

    def local(x, router_w, wg, wu, wd):
        from repro.core.collectives import flat_size

        Bl, Sl, _ = x.shape
        Pn = int(flat_size(axes))
        E_loc = E // Pn
        T = Bl * Sl * K
        C_sd = max(8, -(-int(T / Pn * cfg.capacity_factor) // 8) * 8)
        C_e = max(8, -(-int(Pn * C_sd / E_loc * cfg.capacity_factor) // 8) * 8)

        # 1. routing (fp32)
        logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32), router_w.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = lax.top_k(logits, K)  # (Bl,Sl,K)
        gates = jax.nn.softmax(gate_vals, axis=-1)

        flat_e = expert_idx.reshape(T)
        flat_g = gates.reshape(T)
        xe = jnp.repeat(
            x.reshape(Bl * Sl, d)[:, None, :], K, axis=1
        ).reshape(T, d).astype(dt)

        # 2. destination bucketing
        dest = flat_e // E_loc  # (T,)
        onehot_d = jax.nn.one_hot(dest, Pn, dtype=jnp.int32)  # (T,P)
        pos_d = jnp.sum((jnp.cumsum(onehot_d, axis=0) - onehot_d) * onehot_d, -1)
        keep = pos_d < C_sd
        pos_dc = jnp.where(keep, pos_d, C_sd - 1)

        # dropped entries scatter out-of-bounds with mode="drop" so they can
        # never clobber a live slot.
        pos_oob = jnp.where(keep, pos_dc, C_sd)
        send_x = jnp.zeros((Pn, C_sd, d), dt)
        send_e = jnp.full((Pn, C_sd), -1, jnp.int32)  # local expert id at dest
        send_s = jnp.full((Pn, C_sd), -1, jnp.int32)  # source slot for return
        src_slot = jnp.arange(T, dtype=jnp.int32)
        send_x = send_x.at[dest, pos_oob].add(xe, mode="drop")
        send_e = send_e.at[dest, pos_oob].set(flat_e % E_loc, mode="drop")
        send_s = send_s.at[dest, pos_oob].set(src_slot, mode="drop")

        # 3. exchange: row p of recv_* came from device p
        def a2a(t):
            return lax.all_to_all(t, axes, split_axis=0, concat_axis=0, tiled=True)

        recv_x, recv_e, recv_s = a2a(send_x), a2a(send_e), a2a(send_s)
        R = Pn * C_sd
        rx = recv_x.reshape(R, d)
        re = recv_e.reshape(R)

        # 4. local capacity dispatch to E_loc experts
        valid = re >= 0
        re_c = jnp.where(valid, re, 0)
        onehot_e = jax.nn.one_hot(re_c, E_loc, dtype=jnp.int32) * valid[:, None]
        pos_e = jnp.sum((jnp.cumsum(onehot_e, axis=0) - onehot_e) * onehot_e, -1)
        keep_e = jnp.logical_and(valid, pos_e < C_e)
        pos_ec = jnp.where(keep_e, pos_e, C_e - 1)
        pos_e_oob = jnp.where(keep_e, pos_e, C_e)
        buf = jnp.zeros((E_loc, C_e, d), dt)
        buf = buf.at[re_c, pos_e_oob].add(rx, mode="drop")

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))

        y_ent = yb[re_c, pos_ec] * keep_e.astype(dt)[:, None]  # (R,d)

        # 5. return trip + combine at source
        y_send = y_ent.reshape(Pn, C_sd, d)
        y_recv = a2a(y_send)  # row p: results computed on device p for us
        # Entries we sent to device p came back at the same (p, slot)
        # positions, so our own send_s table maps them home.
        y_tok = jnp.zeros((T, d), dt)
        flat_slot = send_s.reshape(R)
        slot_oob = jnp.where(flat_slot >= 0, flat_slot, T)
        y_tok = y_tok.at[slot_oob].add(y_recv.reshape(R, d), mode="drop")
        y = (y_tok.astype(jnp.float32) * flat_g[:, None]).reshape(
            Bl * Sl, K, d
        ).sum(axis=1).reshape(Bl, Sl, d)

        # aux loss (Switch): the per-expert statistics must be averaged over
        # the GLOBAL token population before taking the product (mean of
        # products != product of means across shards).
        importance = jnp.mean(probs, axis=(0, 1))  # (E,) local
        assigned = jnp.mean(
            jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=2),
            axis=(0, 1),
        )
        importance = lax.pmean(importance, pctx.sp_axes)
        assigned = lax.pmean(assigned, pctx.sp_axes)
        if dp is not None:
            importance = lax.pmean(importance, dp)
            assigned = lax.pmean(assigned, dp)
        aux = E * jnp.sum(importance * assigned) / K
        return y.astype(dt), aux

    fn = shard_map(
        local,
        mesh=pctx.mesh,
        in_specs=(act, rspec, espec, espec, espec),
        out_specs=(act, P_()),
        check_vma=False,
    )
    y, aux = fn(x, p["router"]["w"], p["wg"], p["wu"], p["wd"])
    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x, mlp_type="swiglu", compute_dtype=dt)
    return y, aux


def _moe_ffn_replicated_seq(p, x, cfg, pctx):
    """EP for unshardable-seq inputs (decode): tokens replicated over the EP
    axes; each rank runs its local experts over the entries routed to them
    and the contributions are psum-combined (payload = one (B,1,d) tensor).
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P_

    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.n_experts_per_token
    dt = jnp.dtype(cfg.dtype)
    dp = pctx.data_axis

    if E % pctx.sp_degree == 0:
        ep_axes = pctx.sp_axes if len(pctx.sp_axes) > 1 else pctx.sp_axes[0]
        e_entry = pctx.seq_spec()
    elif E % pctx.mesh.shape["model"] == 0:
        ep_axes = "model"
        e_entry = "model"
    else:
        raise ValueError(f"experts {E} not shardable over {pctx.sp_axes}")

    act = P_(dp, None, None)
    espec = P_(e_entry, None, None)

    def local(x, router_w, wg, wu, wd):
        from repro.core.collectives import flat_rank, flat_size

        Bl = x.shape[0]
        Pn = int(flat_size(ep_axes))
        rank = flat_rank(ep_axes)
        E_loc = E // Pn
        T = Bl * S * K
        C_e = max(8, -(-int(T / E_loc * cfg.capacity_factor) // 8) * 8)

        logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32), router_w.astype(jnp.float32)
        )
        gate_vals, expert_idx = lax.top_k(logits, K)
        gates = jax.nn.softmax(gate_vals, axis=-1)
        flat_e = expert_idx.reshape(T)
        flat_g = gates.reshape(T)
        xe = jnp.repeat(
            x.reshape(Bl * S, d)[:, None, :], K, axis=1
        ).reshape(T, d).astype(dt)

        mine = (flat_e // E_loc) == rank
        le = jnp.where(mine, flat_e % E_loc, 0)
        onehot = jax.nn.one_hot(le, E_loc, dtype=jnp.int32) * mine[:, None]
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
        keep = jnp.logical_and(mine, pos < C_e)
        pos_oob = jnp.where(keep, pos, C_e)
        buf = jnp.zeros((E_loc, C_e, d), dt).at[le, pos_oob].add(xe, mode="drop")

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))
        y_ent = yb[le, jnp.where(keep, pos, C_e - 1)] * keep.astype(dt)[:, None]
        y = (y_ent.astype(jnp.float32) * flat_g[:, None]).reshape(
            Bl * S, K, d
        ).sum(axis=1).reshape(Bl, S, d)
        y = lax.psum(y, ep_axes)
        # replicate over any SP axis not used for EP (pod when E < world)
        return y.astype(dt), jnp.float32(0.0)

    fn = shard_map(
        local,
        mesh=pctx.mesh,
        in_specs=(act, P_(None, None), espec, espec, espec),
        out_specs=(act, P_()),
        check_vma=False,
    )
    y, aux = fn(x, p["router"]["w"], p["wg"], p["wu"], p["wd"])
    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x, mlp_type="swiglu", compute_dtype=dt)
    return y, aux
