"""Dense (and MoE / VLM) decoder-only transformer, scan-over-layers.

Families covered: qwen2-72b, granite-3-8b, qwen3-1.7b, olmo-1b (dense);
qwen3-moe-30b-a3b, llama4-scout (moe, via models.moe); pixtral-12b (vlm —
patch-embedding stub prepended to the token stream).

Implementation notes:
  * layer parameters are stacked (leading L dim) and the layer loop is a
    ``lax.scan`` — one compiled layer body regardless of depth (essential for
    the 512-device dry-run compile times);
  * remat policy per config: "full" (nothing saved), "dots" (matmul outputs
    saved), "none";
  * the LM loss uses chunked cross-entropy — the full (B,S,V) logits tensor
    is never materialized;
  * activations get explicit sharding constraints at block boundaries so XLA
    SPMD keeps the (data, seq) layout stable through the scan.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from repro.models.attention import (
    attention,
    attention_decode,
    attention_decode_paged,
    attention_init,
    attention_prefill_chunk,
    attention_prefill_chunk_paged,
)
from repro.models.layers import (
    apply_norm,
    constrain,
    lm_cross_entropy,
    dense_init,
    embed_init,
    mlp,
    mlp_init,
    norm_init,
)
from repro.models.moe import moe_counters, moe_ffn, moe_init, moe_tally

__all__ = [
    "init_lm",
    "lm_apply",
    "lm_loss",
    "lm_prefill",
    "lm_prefill_chunk",
    "lm_prefill_chunk_paged",
    "lm_decode_step",
    "lm_decode_step_paged",
    "init_decode_cache",
    "init_paged_decode_cache",
    "constrain",
]


def _act_spec(pctx):
    return (pctx.data_axis, pctx.seq_spec(), None)


def _remat_policy(name):
    if name == "none":
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(name)


def _ffn(p_l, h, cfg, pctx, valid=None):
    """The layer's feed-forward: ``(y, aux, counts)``.  With experts, tokens
    outside ``valid`` route to none (see ``moe_ffn``); dense layers return
    no aux loss and no counts."""
    if cfg.n_experts:
        return moe_ffn(p_l["moe"], h, cfg, pctx, valid)
    y = mlp(p_l["mlp"], h, mlp_type=cfg.mlp_type, compute_dtype=jnp.dtype(cfg.dtype))
    return y, jnp.float32(0.0), None


def _moe_zero(cache):
    """Per-step expert counts to accumulate over the layer scan: only where
    the serve state carries the expert counters (``moe_counters``)."""
    if "moe" not in cache:
        return None
    return {"routed": jnp.int32(0), "touched": jnp.int32(0)}


def _moe_add(acc, counts):
    return None if acc is None else {k: acc[k] + counts[k] for k in acc}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _layer_init(key, cfg):
    ka, km, kn = jax.random.split(key, 3)
    p = {
        "attn": attention_init(ka, cfg),
        "ln1": norm_init(cfg.d_model, norm_type=cfg.norm_type, dtype=cfg.param_dtype),
        "ln2": norm_init(cfg.d_model, norm_type=cfg.norm_type, dtype=cfg.param_dtype),
    }
    if cfg.n_experts:
        p["moe"] = moe_init(km, cfg)
    else:
        p["mlp"] = mlp_init(
            km, cfg.d_model, cfg.d_ff, mlp_type=cfg.mlp_type, dtype=cfg.param_dtype
        )
    return p


def init_lm(cfg, key):
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype=cfg.param_dtype),
        "layers": jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys),
        "final_norm": norm_init(
            cfg.d_model, norm_type=cfg.norm_type, dtype=cfg.param_dtype
        ),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            k_head, cfg.d_model, cfg.vocab_size, dtype=cfg.param_dtype
        )
    return params


def _lm_head_w(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["lm_head"]["w"]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _block(p_l, x, positions, cfg, pctx):
    h = apply_norm(p_l["ln1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    x = x + attention(
        p_l["attn"], h, positions, cfg=cfg, pctx=pctx, window=cfg.window
    )
    x = constrain(x, pctx, _act_spec(pctx))
    h = apply_norm(p_l["ln2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    y, aux, _ = _ffn(p_l, h, cfg, pctx)
    x = x + y
    x = constrain(x, pctx, _act_spec(pctx))
    return x, aux


def _embed_inputs(params, tokens, cfg, pctx, prefix_embeds=None):
    x = params["embed"]["table"][tokens].astype(jnp.dtype(cfg.dtype))
    if prefix_embeds is not None:
        # VLM stub frontend: patch embeddings occupy the first slots.
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


def lm_apply(params, tokens, positions, *, cfg, pctx, prefix_embeds=None):
    """Full forward, returns final hidden states ``(B, S, d)``."""
    x = _embed_inputs(params, tokens, cfg, pctx, prefix_embeds)
    x = constrain(x, pctx, _act_spec(pctx))

    block = partial(_block, cfg=cfg, pctx=pctx)
    policy = _remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(
            lambda p_l, x, pos: _block(p_l, x, pos, cfg, pctx), policy=policy
        )
    else:
        block = lambda p_l, x, pos: _block(p_l, x, pos, cfg, pctx)  # noqa: E731

    def body(carry, p_l):
        x, aux = carry
        x, a = block(p_l, x, positions)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    x = apply_norm(params["final_norm"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    return x, aux


def lm_loss(params, batch, *, cfg, pctx):
    """Causal LM loss; batch: tokens/labels/positions (+mask, +patch_embeds)."""
    x, aux = lm_apply(
        params,
        batch["tokens"],
        batch["positions"],
        cfg=cfg,
        pctx=pctx,
        prefix_embeds=batch.get("patch_embeds"),
    )
    labels = batch["labels"]
    mask = batch.get("mask")
    if batch.get("patch_embeds") is not None:
        # Image-prefix positions carry no LM loss.
        n_img = batch["patch_embeds"].shape[1]
        B = labels.shape[0]
        pad_lbl = jnp.zeros((B, n_img), labels.dtype)
        labels = jnp.concatenate([pad_lbl, labels], axis=1)
        m = jnp.concatenate(
            [jnp.zeros((B, n_img), jnp.float32),
             jnp.ones_like(batch["labels"], jnp.float32) if mask is None else mask],
            axis=1,
        )
        mask = m
    loss, denom = lm_cross_entropy(
        x,
        _lm_head_w(params, cfg).astype(jnp.dtype(cfg.dtype)),
        labels,
        mask=mask,
        chunk=cfg.logits_chunk,
        compute_dtype=jnp.dtype(cfg.dtype),
        pctx=pctx,
    )
    total = loss + cfg.router_aux_coef * aux / max(cfg.n_layers, 1)
    metrics = {"ce_loss": loss, "aux_loss": aux, "tokens": denom}
    return total, metrics


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def init_decode_cache(cfg, batch: int, max_len: int, pctx, dtype=None):
    """Stacked-over-layers KV cache pytree (positions at PAD sentinel)."""
    from repro.kernels.flash_attention import PAD_POS

    dtype = jnp.dtype(dtype or cfg.dtype)
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((L, batch, max_len, Hkv, Dh), dtype),
        "v": jnp.zeros((L, batch, max_len, Hkv, Dh), dtype),
        "pos": jnp.full((batch, max_len), PAD_POS, jnp.int32),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def lm_prefill(params, tokens, positions, cache, prefix_embeds=None, *, cfg, pctx):
    """Prefill: run the full sequence, fill cache slots [0, S)."""
    x = _embed_inputs(params, tokens, cfg, pctx, prefix_embeds)
    x = constrain(x, pctx, _act_spec(pctx))
    S = x.shape[1]

    def body(carry, xs):
        x = carry
        p_l, kc_l, vc_l = xs
        h = apply_norm(p_l["ln1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, new_cache = attention(
            p_l["attn"], h, positions, cfg=cfg, pctx=pctx, window=cfg.window,
            cache={"k": kc_l, "v": vc_l, "pos": positions},
        )
        x = x + y
        h = apply_norm(p_l["ln2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, _, _ = _ffn(p_l, h, cfg, pctx)
        x = constrain(x + y, pctx, _act_spec(pctx))
        return x, (new_cache["k"], new_cache["v"])

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = apply_norm(params["final_norm"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    last = x[:, -1:, :]
    logits = jnp.einsum(
        "bsd,dv->bsv", last.astype(jnp.dtype(cfg.dtype)),
        _lm_head_w(params, cfg).astype(jnp.dtype(cfg.dtype)),
    )
    B = tokens.shape[0]
    new_cache = {
        "k": ks,
        "v": vs,
        "pos": jax.lax.dynamic_update_slice(cache["pos"], positions, (0, 0)),
        "len": jnp.full((B,), S, jnp.int32),
    }
    return logits[:, 0], new_cache


def lm_prefill_chunk(params, token_ids, cache, n_valid, *, cfg, pctx):
    """Chunked prefill: append ``token_ids (B, C)`` to per-request caches.

    ``n_valid (B,)``: how many of the ``C`` chunk slots are real prompt
    tokens per request — ``0`` skips a row entirely (its cache, positions,
    and length are untouched), a value ``< C`` handles the prompt tail
    without retracing (the engine always calls with one static ``C``).

    Row ``b``'s valid tokens land in cache slots ``[len_b, len_b+n_valid_b)``
    and attend to (a) the resident cache of all previous chunks and (b) the
    chunk itself, causally — the two partials are merged with the paper's
    Update() equations (see ``core/decode.py``), so a chunk-size sweep is
    numerically the one-shot prefill.  Returns ``(logits, new_cache)`` with
    ``logits (B, V)`` taken at each row's last valid position (garbage for
    skipped rows).
    """
    B, C = token_ids.shape
    Smax = cache["pos"].shape[1]
    length = cache["len"]  # (B,)
    offs = jnp.arange(C, dtype=jnp.int32)[None, :]  # (1, C)
    positions = length[:, None].astype(jnp.int32) + offs  # (B, C)
    valid = offs < n_valid[:, None]  # (B, C)
    # Invalid slots write out of range -> dropped by scatter mode="drop".
    write_index = jnp.where(valid, length[:, None] + offs, Smax)
    x = params["embed"]["table"][token_ids].astype(jnp.dtype(cfg.dtype))
    old_pos = cache["pos"]  # pre-chunk view: resident partial must not see
    # the chunk's own slots (they are attended locally, pre-write)

    def body(x, xs):
        p_l, kc_l, vc_l = xs
        h = apply_norm(p_l["ln1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, kc_l, vc_l = attention_prefill_chunk(
            p_l["attn"], h, positions, kc_l, vc_l, old_pos, write_index,
            cfg=cfg, pctx=pctx, window=cfg.window,
        )
        x = x + y
        h = apply_norm(p_l["ln2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, _, _ = _ffn(p_l, h, cfg, pctx, valid)
        return x + y, (kc_l, vc_l)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = apply_norm(params["final_norm"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    last_idx = jnp.clip(n_valid - 1, 0, C - 1)
    last = x[jnp.arange(B), last_idx]  # (B, d) — last valid chunk position
    logits = jnp.einsum(
        "bd,dv->bv", last.astype(jnp.dtype(cfg.dtype)),
        _lm_head_w(params, cfg).astype(jnp.dtype(cfg.dtype)),
    )
    new_cache = {
        "k": ks,
        "v": vs,
        "pos": old_pos.at[jnp.arange(B)[:, None], write_index].set(
            positions, mode="drop"
        ),
        "len": length + n_valid.astype(length.dtype),
    }
    return logits, new_cache


def init_paged_decode_cache(
    cfg, *, n_pages: int, page_size: int, max_batch: int, slot_pages: int,
    pctx=None, dtype=None,
):
    """Page-pool serve state (see ``serving/kv_cache.py`` for the layout).

    Physical memory is ``n_pages * page_size`` tokens shared by every slot;
    each slot's logical capacity is ``slot_pages * page_size``.  A model
    with experts on one device also carries ``moe``, the expert layer's
    counters (``moe_counters``), which both paged steps advance.  Under a mesh
    the page dimension shards over the SP axes, so block tables wider than
    one device's page budget stripe the prompt across the ring.
    """
    from repro.serving.kv_cache import init_paged_cache

    state = init_paged_cache(
        cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, n_pages=n_pages,
        page_size=page_size, max_batch=max_batch, slot_pages=slot_pages,
        dtype=dtype or cfg.dtype, pctx=pctx,
    )
    if cfg.n_experts and not (pctx is not None and pctx.active):
        state["moe"] = moe_counters()
    return state


def lm_prefill_chunk_paged(
    params, token_ids, cache, n_valid, row_slot=None, row_start=None, *, cfg, pctx
):
    """Paged chunked prefill: the page-pool analog of :func:`lm_prefill_chunk`.

    Same contract (``token_ids (B, C)``, ``n_valid (B,)``, skipped rows
    untouched, logits at each row's last valid position) — except that a
    row is not tied to a slot.  ``row_slot (B,)`` names the slot whose block
    table row ``b`` writes through and ``row_start (B,)`` its first position
    (defaults: the identity and ``cache["len"]``, one row per slot).  Several
    rows may carry consecutive chunks of one slot's prompt: all rows write
    their K/V first, then each attends to every position before its own
    start, those written by earlier rows of this step included.  A slot's
    ``len`` becomes the largest ``row_start + n_valid`` over its rows with
    tokens.  The engine guarantees every written table entry is mapped
    before calling (admission allocates prompt pages); unmapped entries drop
    the write and mask the read, so a bookkeeping bug degrades to masked
    garbage, never to a write on someone else's page.
    """
    from repro.kernels.flash_attention import PAD_POS
    from repro.serving.kv_cache import gather_positions, view_indices, write_coords

    B, C = token_ids.shape
    n_pages, page_size = cache["pos"].shape
    length = cache["len"]  # (n_slots,)
    if row_slot is None:
        row_slot = jnp.arange(B, dtype=jnp.int32)
    if row_start is None:
        row_start = length[row_slot]
    bt = cache["block_tables"][row_slot]  # (B, W): each row's slot's table
    offs = jnp.arange(C, dtype=jnp.int32)[None, :]
    positions = row_start[:, None] + offs  # (B, C)
    valid = offs < n_valid[:, None]
    write_page, write_off = write_coords(
        bt, positions, valid, n_pages, page_size
    )
    pos_pool = cache["pos"].at[write_page, write_off].set(positions, mode="drop")
    # Resident view clamped to the pages the row's start actually uses:
    # stale mappings beyond it gather as fill, never as data.  Positions at
    # or past the start (the row's own tokens and later rows') are masked:
    # the row attends its own chunk locally, and later chunks not at all.
    flat_view = view_indices(bt, page_size, lengths=row_start)
    pos_view = gather_positions(pos_pool, flat_view)
    pos_view = jnp.where(pos_view < row_start[:, None], pos_view, PAD_POS)
    x = params["embed"]["table"][token_ids].astype(jnp.dtype(cfg.dtype))

    def body(carry, xs):
        x, n = carry
        p_l, kc_l, vc_l = xs
        h = apply_norm(p_l["ln1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, kc_l, vc_l = attention_prefill_chunk_paged(
            p_l["attn"], h, positions, kc_l, vc_l, pos_view, flat_view,
            write_page, write_off, cfg=cfg, pctx=pctx, window=cfg.window,
            table_pages=bt.shape[1],
        )
        x = x + y
        h = apply_norm(p_l["ln2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, _, counts = _ffn(p_l, h, cfg, pctx, valid)
        return (x + y, _moe_add(n, counts)), (kc_l, vc_l)

    (x, n), (ks, vs) = jax.lax.scan(
        body, (x, _moe_zero(cache)), (params["layers"], cache["k"], cache["v"])
    )
    x = apply_norm(params["final_norm"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    last_idx = jnp.clip(n_valid - 1, 0, C - 1)
    last = x[jnp.arange(B), last_idx]
    logits = jnp.einsum(
        "bd,dv->bv", last.astype(jnp.dtype(cfg.dtype)),
        _lm_head_w(params, cfg).astype(jnp.dtype(cfg.dtype)),
    )
    # Rows without tokens scatter to an out-of-range slot and drop.
    owner = jnp.where(n_valid > 0, row_slot, length.shape[0])
    new_cache = {
        "k": ks,
        "v": vs,
        "pos": pos_pool,
        "block_tables": cache["block_tables"],
        "len": length.at[owner].max(
            (row_start + n_valid).astype(length.dtype), mode="drop"
        ),
    }
    if n is not None:
        new_cache["moe"] = moe_tally(cache["moe"], n)
    return logits, new_cache


def lm_decode_step_paged(params, token_ids, cache, active=None, *, cfg, pctx):
    """Paged decode step: the page-pool analog of :func:`lm_decode_step`.

    Identical contract (``token_ids (B,)`` -> ``logits (B, V)``, ``active``
    rows only); the new token's K/V land at the physical ``(page, offset)``
    its block table maps for logical slot ``len[b]``.  Attention consumes
    the pool *through the block table* (``attention_decode_paged`` — the
    fused Pallas kernel on pallas impls, the lengths-clamped gather oracle
    on xla); no dense view is built here.
    """
    from repro.serving.kv_cache import write_coords

    B = token_ids.shape[0]
    n_pages, page_size = cache["pos"].shape
    bt = cache["block_tables"]
    length = cache["len"]  # (B,)
    if active is None:
        valid = jnp.ones((B,), bool)
        new_len = length + 1
    else:
        valid = active
        new_len = jnp.where(active, length + 1, length)
    write_page, write_off = write_coords(bt, length, valid, n_pages, page_size)
    positions = length[:, None].astype(jnp.int32)  # global pos == length
    pos_pool = cache["pos"].at[write_page, write_off].set(
        positions[:, 0], mode="drop"
    )  # includes the new token
    x = params["embed"]["table"][token_ids[:, None]].astype(jnp.dtype(cfg.dtype))

    def body(carry, xs):
        x, n = carry
        p_l, kc_l, vc_l = xs
        h = apply_norm(p_l["ln1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, kc_l, vc_l = attention_decode_paged(
            p_l["attn"], h, positions, kc_l, vc_l, pos_pool, bt, new_len,
            write_page, write_off, cfg=cfg, pctx=pctx, window=cfg.window,
            table_pages=bt.shape[1],
        )
        x = x + y
        h = apply_norm(p_l["ln2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, _, counts = _ffn(p_l, h, cfg, pctx, valid[:, None])
        return (x + y, _moe_add(n, counts)), (kc_l, vc_l)

    (x, n), (ks, vs) = jax.lax.scan(
        body, (x, _moe_zero(cache)), (params["layers"], cache["k"], cache["v"])
    )
    x = apply_norm(params["final_norm"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x.astype(jnp.dtype(cfg.dtype)),
        _lm_head_w(params, cfg).astype(jnp.dtype(cfg.dtype)),
    )[:, 0]
    new_cache = {
        "k": ks, "v": vs, "pos": pos_pool, "block_tables": bt, "len": new_len,
    }
    if n is not None:
        new_cache["moe"] = moe_tally(cache["moe"], n)
    return logits, new_cache


def lm_decode_step(params, token_ids, cache, active=None, *, cfg, pctx):
    """One decode step for all requests: ``token_ids (B,)`` -> logits (B,V).

    Per-request cache lengths (continuous batching): new K/V are written at
    ``cache['len']`` slots, positions advance independently.  ``active
    (B,)`` (bool, optional) skips rows entirely — no cache write, no length
    advance — so decode steps interleave with rows still mid-prefill without
    any rollback bookkeeping.
    """
    B = token_ids.shape[0]
    Smax = cache["pos"].shape[1]
    length = cache["len"]  # (B,)
    if active is None:
        write_index = length
        new_len = length + 1
    else:
        # Inactive rows write out of range (dropped) and keep their length.
        write_index = jnp.where(active, length, Smax)
        new_len = jnp.where(active, length + 1, length)
    positions = length[:, None].astype(jnp.int32)  # global pos == length
    x = params["embed"]["table"][token_ids[:, None]].astype(jnp.dtype(cfg.dtype))

    pos_cache = cache["pos"].at[jnp.arange(B), write_index].set(
        positions[:, 0], mode="drop"
    )

    def body(x, xs):
        p_l, kc_l, vc_l = xs
        h = apply_norm(p_l["ln1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, kc_l, vc_l = attention_decode(
            p_l["attn"], h, positions, kc_l, vc_l, pos_cache, write_index,
            cfg=cfg, pctx=pctx, window=cfg.window,
        )
        x = x + y
        h = apply_norm(p_l["ln2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        y, _, _ = _ffn(p_l, h, cfg, pctx, None if active is None else active[:, None])
        return x + y, (kc_l, vc_l)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = apply_norm(params["final_norm"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x.astype(jnp.dtype(cfg.dtype)),
        _lm_head_w(params, cfg).astype(jnp.dtype(cfg.dtype)),
    )[:, 0]
    new_cache = {"k": ks, "v": vs, "pos": pos_cache, "len": new_len}
    return logits, new_cache
