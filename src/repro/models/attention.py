"""Model-level attention layer: projections + RoPE + SP attention core.

Entry points sharing one parameter set:
  * ``attention``        — training / one-shot prefill self-attention
                           (optionally filling a KV cache),
  * ``attention_prefill_chunk`` — a C-token prompt chunk against the resident
                           cache (serving chunked prefill; writes the chunk's
                           K/V into per-request cache regions),
  * ``attention_decode`` — single-token decode against a sharded cache,
  * ``attention_decode_paged`` / ``attention_prefill_chunk_paged`` — the same
                           two serving steps against the paged page pool
                           (``serving/kv_cache.py``): scatter into owned
                           pages of the stacked pool at the layer's index,
                           read the block-table view, and run the identical
                           SP attention — a paged read is numerically the
                           dense read,
  * ``cross_attention``  — encoder-decoder cross attention (resident KV =
                           TokenRing's natural fit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.api import (
    ParallelContext,
    sp_attention,
    sp_decode,
    sp_decode_paged,
    sp_prefill,
)
from repro.models.layers import (
    apply_norm,
    apply_rope,
    constrain,
    dense,
    dense_init,
    norm_init,
)

__all__ = [
    "attention_init",
    "attention",
    "attention_prefill_chunk",
    "attention_prefill_chunk_paged",
    "attention_decode",
    "attention_decode_paged",
    "cross_attention",
]


def attention_init(key, cfg):
    d, Hq, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, Hq * Dh, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wk": dense_init(ks[1], d, Hkv * Dh, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wv": dense_init(ks[2], d, Hkv * Dh, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wo": dense_init(ks[3], Hq * Dh, d, dtype=cfg.param_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(Dh, norm_type="rmsnorm", dtype=cfg.param_dtype)
        p["k_norm"] = norm_init(Dh, norm_type="rmsnorm", dtype=cfg.param_dtype)
    return p


def _project_qkv(p, x, positions, cfg, rope: bool = True, pctx=None):
    from repro.sharding import constrain_act

    B, S, _ = x.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    q = constrain_act(dense(p["wq"], x, dt), pctx).reshape(B, S, Hq, Dh)
    k = constrain_act(dense(p["wk"], x, dt), pctx).reshape(B, S, Hkv, Dh)
    v = constrain_act(dense(p["wv"], x, dt), pctx).reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, norm_type="rmsnorm", eps=cfg.norm_eps)
        k = apply_norm(p["k_norm"], k, norm_type="rmsnorm", eps=cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention(
    p,
    x,
    positions,
    *,
    cfg,
    pctx: ParallelContext,
    window: int | None = None,
    causal: bool | None = None,
    rope: bool = True,
    cache=None,
):
    """Self-attention over ``x (B,S,d)`` with global ``positions (B,S)``.

    If ``cache`` (dict with k/v/pos) is given, returns ``(y, new_cache)`` —
    the prefill path: computed K/V overwrite the first ``S`` cache slots.
    """
    B, S, d = x.shape
    causal = cfg.causal if causal is None else causal
    q, k, v = _project_qkv(p, x, positions, cfg, rope=rope, pctx=pctx)
    out = sp_attention(
        q, k, v, positions, positions, pctx=pctx, causal=causal, window=window
    )
    y = dense(p["wo"], out.reshape(B, S, -1), jnp.dtype(cfg.dtype))
    if cache is None:
        return y
    new_cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        "pos": jax.lax.dynamic_update_slice(cache["pos"], positions, (0, 0)),
    }
    return y, new_cache


def attention_prefill_chunk(
    p,
    x,
    positions,
    k_cache,
    v_cache,
    pos_cache,
    write_index,
    *,
    cfg,
    pctx: ParallelContext,
    window: int | None = None,
    rope: bool = True,
):
    """Chunked-prefill step: ``x (B,C,d)`` appended to per-request caches.

    ``positions (B,C)``: global positions of the chunk tokens per request
    (rows being skipped may carry arbitrary values — their writes are
    dropped).  ``pos_cache (B,Smax)``: position table, already updated for
    this chunk (shared across layers).  ``write_index (B,C)``: cache slots to
    write, with out-of-range values (>= Smax) for rows/tokens that must not
    land (inactive slots, chunk-tail padding) — dropped by scatter mode.

    The chunk's attention is the two-partial Update() merge (``sp_prefill``):
    chunk queries vs the resident cache (every *previous* chunk) plus the
    chunk's own causal block; its K/V are written to the cache afterwards.
    Returns ``(y, k_cache', v_cache')``.
    """
    B, C, _ = x.shape
    q, k, v = _project_qkv(p, x, positions, cfg, rope=rope, pctx=pctx)
    out = sp_prefill(
        q, k, v, positions, k_cache, v_cache, pos_cache, positions,
        pctx=pctx, window=window,
    )
    bidx = jnp.arange(B)[:, None]
    kc = k_cache.at[bidx, write_index].set(k.astype(k_cache.dtype), mode="drop")
    vc = v_cache.at[bidx, write_index].set(v.astype(v_cache.dtype), mode="drop")
    y = dense(p["wo"], out.reshape(B, C, -1), jnp.dtype(cfg.dtype))
    return y, kc, vc


def attention_decode(
    p,
    x,
    positions,
    k_cache,
    v_cache,
    pos_cache,
    write_index,
    *,
    cfg,
    pctx: ParallelContext,
    window: int | None = None,
    rope: bool = True,
):
    """Decode step: ``x (B,1,d)``; cache k/v ``(B,Smax,Hkv,D)`` seq-sharded.

    ``positions (B,1)``: the global position of the new token per request.
    ``pos_cache (B,Smax)``: position table (already updated for this step —
    it is shared across layers).  ``write_index (B,)``: cache slot to write;
    per-request slots enable continuous batching, out-of-range values
    (>= Smax, rows skipped this step) are dropped.
    Returns ``(y, k_cache', v_cache')``.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, positions, cfg, rope=rope, pctx=pctx)
    bidx = jnp.arange(B)
    kc = k_cache.at[bidx, write_index].set(
        k[:, 0].astype(k_cache.dtype), mode="drop"
    )
    vc = v_cache.at[bidx, write_index].set(
        v[:, 0].astype(v_cache.dtype), mode="drop"
    )
    out = sp_decode(q, kc, vc, pos_cache, positions, pctx=pctx, window=window)
    y = dense(p["wo"], out.reshape(B, S, -1), jnp.dtype(cfg.dtype))
    return y, kc, vc


def _view_spec(pctx):
    """Spec of a gathered page view: the same (data, seq) layout as a dense
    cache, so the decode/prefill plans shard it identically."""
    return (pctx.data_axis, pctx.seq_spec(), None, None)


def attention_decode_paged(
    p,
    x,
    positions,
    k_pool,
    v_pool,
    layer,
    pos_pool,
    block_tables,
    lengths,
    write_page,
    write_off,
    *,
    cfg,
    pctx: ParallelContext,
    window: int | None = None,
    rope: bool = True,
    table_pages: int | None = None,
):
    """Paged decode step: ``x (B,1,d)``; the stacked pools
    ``(L,n_pages,ps,Hkv,D)`` at layer ``layer`` (int32 scalar).

    The new K/V scatter into the whole pool at ``[layer, write_page,
    write_off]``, so a layer scan that carries the pool updates it in place;
    the kernel reads the layer where it lies.  ``pos_pool (n_pages, ps)`` is
    the position pool *already updated* for this step (shared across
    layers); ``block_tables (B, W)`` the slots' page maps; ``lengths (B,)``
    the post-write used lengths.
    ``write_page``/``write_off (B,)`` locate the new token's physical slot
    (``n_pages`` sentinel drops skipped rows).  The new K/V scatter into the
    pool first, then attention dispatches on the resolved kernel impl:

      * pallas / pallas_interpret — the fused paged-decode kernel
        (``kernels/paged_attention.py``) reads pages in place through the
        scalar-prefetched block table; **no gathered dense view exists**.
      * xla — the oracle: gather the block-table view (clamped by
        ``lengths`` to the pages actually used) and run the *same*
        ``sp_decode`` as the dense path.

    ``table_pages`` (block-table width) rides into the plan's cost term
    either way.  Returns ``(y, k_pool', v_pool')``.
    """
    from repro.kernels.ops import FlashConfig
    from repro.serving.kv_cache import gather_pages, gather_positions, view_indices

    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, positions, cfg, rope=rope, pctx=pctx)
    kp = k_pool.at[layer, write_page, write_off].set(
        k[:, 0].astype(k_pool.dtype), mode="drop")
    vp = v_pool.at[layer, write_page, write_off].set(
        v[:, 0].astype(v_pool.dtype), mode="drop")
    if FlashConfig(impl=pctx.impl).resolve_impl() == "xla":
        page_size = pos_pool.shape[1]
        flat_view = view_indices(block_tables, page_size, lengths=lengths)
        pos_view = gather_positions(pos_pool, flat_view)
        k_view = constrain(gather_pages(kp, flat_view, layer), pctx, _view_spec(pctx))
        v_view = constrain(gather_pages(vp, flat_view, layer), pctx, _view_spec(pctx))
        out = sp_decode(
            q, k_view, v_view, pos_view, positions, pctx=pctx, window=window,
            table_pages=table_pages,
        )
    else:
        out = sp_decode_paged(
            q, kp, vp, layer, pos_pool, block_tables, positions, lengths,
            pctx=pctx, window=window, table_pages=table_pages,
        )
    y = dense(p["wo"], out.reshape(B, S, -1), jnp.dtype(cfg.dtype))
    return y, kp, vp


def attention_prefill_chunk_paged(
    p,
    x,
    positions,
    k_pool,
    v_pool,
    layer,
    pos_view,
    flat_view,
    write_page,
    write_off,
    *,
    cfg,
    pctx: ParallelContext,
    window: int | None = None,
    rope: bool = True,
    table_pages: int | None = None,
):
    """Paged chunked-prefill step: ``x (B,C,d)`` against the gathered view.

    Pools are the stacked ``(L,n_pages,ps,Hkv,D)`` at layer ``layer``, as in
    :func:`attention_decode_paged`.  Every row's chunk K/V scatter into the
    pool first (``write_page``/``write_off (B,C)`` carry the drop sentinel
    for invalid tokens); then each row gathers its resident view from the
    *written* pool, so a row sees what earlier rows of the same step wrote.
    ``pos_view`` holds only the positions before each row's own start
    (``PAD_POS`` elsewhere): the resident partial never sees the row's own
    tokens, which are attended locally inside ``sp_prefill``.  Returns
    ``(y, k_pool', v_pool')``.
    """
    from repro.serving.kv_cache import gather_pages

    B, C, _ = x.shape
    q, k, v = _project_qkv(p, x, positions, cfg, rope=rope, pctx=pctx)
    kp = k_pool.at[layer, write_page, write_off].set(k.astype(k_pool.dtype), mode="drop")
    vp = v_pool.at[layer, write_page, write_off].set(v.astype(v_pool.dtype), mode="drop")
    k_view = constrain(gather_pages(kp, flat_view, layer), pctx, _view_spec(pctx))
    v_view = constrain(gather_pages(vp, flat_view, layer), pctx, _view_spec(pctx))
    out = sp_prefill(
        q, k, v, positions, k_view, v_view, pos_view, positions,
        pctx=pctx, window=window, table_pages=table_pages,
    )
    y = dense(p["wo"], out.reshape(B, C, -1), jnp.dtype(cfg.dtype))
    return y, kp, vp


def cross_attention(
    p,
    x,
    enc_k,
    enc_v,
    enc_pos,
    positions,
    *,
    cfg,
    pctx: ParallelContext,
):
    """Cross-attention: queries from the decoder stream, resident encoder KV.

    ``enc_k/enc_v (B,S_enc,Hkv,D)`` are precomputed (by ``encode_kv``) and
    stay sequence-sharded — the decode-side uses sp_decode (tiny q), the
    prefill side uses sp_attention non-causally.
    """
    B, S, d = x.shape
    dt = jnp.dtype(cfg.dtype)
    Hq, Dh = cfg.n_heads, cfg.head_dim
    q = dense(p["wq"], x, dt).reshape(B, S, Hq, Dh)
    if S == 1:
        out = sp_decode(q, enc_k, enc_v, enc_pos, positions, pctx=pctx)
    else:
        out = sp_attention(
            q, enc_k, enc_v, positions, enc_pos, pctx=pctx, causal=False
        )
    return dense(p["wo"], out.reshape(B, S, -1), dt)


def encode_kv(p, enc_x, cfg):
    """Precompute cross-attention K/V from encoder outputs (no RoPE)."""
    B, S, _ = enc_x.shape
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    k = dense(p["wk"], enc_x, dt).reshape(B, S, Hkv, Dh)
    v = dense(p["wv"], enc_x, dt).reshape(B, S, Hkv, Dh)
    return k, v
