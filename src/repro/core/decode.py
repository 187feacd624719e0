"""Sequence-parallel decode & chunked-prefill attention (TokenRing's serving
face).

During serving the KV cache is enormous (up to 512k tokens here) while the
query side is tiny — one token per request in decode, one prompt *chunk* in
prefill.  TokenRing's premise — *keep KV resident, move the small side* —
becomes exact: the cache stays sequence-sharded forever, the small Q is
replicated, every device computes a partial ``(out, lse)`` against its cache
shard with the flash kernel, and the partials are merged across the SP axes
with the paper's Update() equations (``core/merge.py``), realized here as an
lse-weighted ``psum`` (distributed flash-decoding).

Two schedules, both registered as first-class ``SPStrategy`` entries so
``ParallelContext.plan_decode`` / ``plan_prefill`` price them with the same
cost-model machinery the training planner uses:

``"decode"``  — ``sp_decode_attention``: 1-token Q (``Sq`` small), psum merge.
  Per-token communication: ``B * Hq * (D + 2)`` fp32 scalars (num ``D``,
  denom ``1``, lse-pmax ``1``) — independent of context length.  Ring
  Attention in the same role would rotate the cache itself.

``"prefill"`` — ``sp_prefill_chunk_attention``: a C-token prompt chunk
  attends to (a) the resident sharded cache of all *previous* chunks (same
  psum merge, C query rows) and (b) its own replicated K/V, causally, as a
  free local partial.  The two partials are combined with
  :func:`repro.core.merge.merge_partials` — cross-chunk causality is exactly
  the online-softmax Update(), so chunked prefill is numerically the one-shot
  prefill.  Per-chunk communication: ``B * C * Hq * (D + 2)`` fp32 scalars,
  i.e. a prompt costs ``O(S)`` psum bytes total versus a KV ring's
  ``O(S^2 / chunk)`` rotated-cache bytes (the cache re-circulates every
  chunk) — the planner arithmetic behind chunk-resident serving.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core.merge import finalize, merge_partials
from repro.core.strategies import CommCost, register_strategy
from repro.kernels.ops import flash_attention, paged_decode_attention

__all__ = [
    "sp_decode_attention",
    "sp_paged_decode_attention",
    "sp_prefill_chunk_attention",
    "psum_merge_partials",
    "decode_comm_cost",
    "prefill_comm_cost",
]


def psum_merge_partials(out, lse, axis_names):
    """Merge per-device attention partials across the SP axes.

    The paper's Update() specialized to an all-reduce: with per-device
    ``w_i = exp(lse_i - max_j lse_j)``,

        out = sum_i w_i * out_i / sum_i w_i
        lse = max_j lse_j + log(sum_i w_i)

    Empty partials (``lse = -inf``, fully-masked cache shards) contribute
    ``w = 0``.  Returns a *mergeable* ``(out, lse)`` pair — callers holding
    more partials (e.g. a prompt chunk's local block) combine them with
    :func:`repro.core.merge.merge_partials`; rows that attended to nothing
    come back as the empty partial ``(0, -inf)``.

    Wire cost per call: psum of ``(..., Hq, D+1)`` plus pmax of
    ``(..., Hq)`` — all fp32, independent of the cache length.
    """
    m = lax.pmax(lse, axis_names)
    w = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, lse - m))
    w = jnp.where(jnp.isneginf(lse), 0.0, w)
    num = lax.psum(w[..., None] * out.astype(jnp.float32), axis_names)
    den = lax.psum(w, axis_names)
    safe = den > 0.0
    merged = num / jnp.where(safe, den, 1.0)[..., None]
    merged = jnp.where(safe[..., None], merged, 0.0).astype(out.dtype)
    merged_lse = jnp.where(safe, m + jnp.log(jnp.where(safe, den, 1.0)), -jnp.inf)
    return merged, merged_lse


def sp_decode_attention(
    q,
    k_cache,
    v_cache,
    k_pos,
    *,
    axis_names,
    q_pos=None,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    impl: str = "auto",
    block_k: int = 512,
    return_lse: bool = False,
):
    """Decode attention inside shard_map.

    ``q``: (B, Sq, Hq, D) with small Sq (1 for decode, a chunk for prefill),
    replicated over the SP axes.  ``k_cache``/``v_cache``: (B, S_loc, Hkv, D)
    sequence shards.  ``k_pos``: (B, S_loc) global positions; unwritten cache
    slots carry the PAD_POS sentinel and are masked inside the kernel.
    Returns (B, Sq, Hq, D) replicated over the SP axes — plus the merged lse
    (B, Sq, Hq) when ``return_lse`` (a mergeable partial for cross-chunk
    accumulation via ``core/merge.py``).
    """
    B, Sq, Hq, D = q.shape
    if q_pos is None:
        # Caller should pass real positions; default to "after everything".
        q_pos = jnp.full((B, Sq), 2**29 - 1, jnp.int32)

    out, lse = flash_attention(
        q, k_cache, v_cache, q_pos=q_pos, k_pos=k_pos, causal=causal,
        window=window, scale=scale, impl=impl, block_q=max(Sq, 1),
        block_k=block_k,
    )
    if axis_names:
        merged, merged_lse = psum_merge_partials(out, lse, axis_names)
    else:
        # Single device (or outside shard_map): the local partial is total.
        merged, merged_lse = finalize(out, lse)
    merged = merged.astype(q.dtype)
    return (merged, merged_lse) if return_lse else merged


def sp_paged_decode_attention(
    q,
    k_pool,
    v_pool,
    pos_pool,
    block_tables,
    q_pos,
    layer,
    *,
    axis_names,
    lengths=None,
    window: int | None = None,
    scale: float | None = None,
    impl: str = "auto",
    block_k: int | None = None,
    return_lse: bool = False,
):
    """Paged decode attention inside shard_map — fused kernel per shard.

    The page-pool analogue of :func:`sp_decode_attention`: the pool never
    re-materializes into a dense view.  Each device owns one *contiguous*
    stripe of ``n_local = n_pages / P`` pool pages (``NamedSharding`` blocks
    the page dimension contiguously across the SP axes, see
    ``serving/kv_cache.py::init_paged_cache``), so shard ``idx`` holds global
    pages ``[idx * n_local, (idx + 1) * n_local)``.  The replicated global
    block tables are remapped into the local page space — an entry outside
    the stripe (another shard's page, or the global ``n_pages`` sentinel,
    which is ``>= lo + n_local`` on every shard) becomes the local sentinel
    ``n_local`` — and each shard's :func:`paged_decode_attention` partial
    covers exactly the pages it holds; the partials merge with the same
    lse-weighted psum as dense decode (identical wire bytes, so the
    registered ``"decode"`` cost row prices both paths).

    ``q (B, Sq=1, Hq, D)`` and ``q_pos (B, 1)`` replicated over the SP axes;
    stacked pools ``k_pool``/``v_pool (L, n_local, page_size, Hkv, D)``, read
    at ``layer``, and ``pos_pool (n_local, page_size)`` page-sharded;
    ``block_tables (B, W)`` global page ids.  Returns the merged ``(B, Sq,
    Hq, D)`` (plus merged lse when ``return_lse``).
    """
    n_local = k_pool.shape[1]
    bt = block_tables.astype(jnp.int32)
    if axis_names:
        idx = jnp.int32(0)
        for ax in axis_names:
            idx = idx * lax.psum(1, ax) + lax.axis_index(ax)
        lo = idx * n_local
        bt = jnp.where(
            jnp.logical_and(bt >= lo, bt < lo + n_local), bt - lo, n_local
        )
    out, lse = paged_decode_attention(
        q, k_pool, v_pool, pos_pool, bt, q_pos, layer,
        lengths=lengths, window=window, scale=scale, block_k=block_k,
        impl=impl,
    )
    if axis_names:
        merged, merged_lse = psum_merge_partials(out, lse, axis_names)
    else:
        merged, merged_lse = finalize(out, lse)
    merged = merged.astype(q.dtype)
    return (merged, merged_lse) if return_lse else merged


def sp_prefill_chunk_attention(
    q,
    k_new,
    v_new,
    new_pos,
    k_cache,
    v_cache,
    k_pos,
    *,
    axis_names,
    q_pos,
    window: int | None = None,
    scale: float | None = None,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
    return_lse: bool = False,
):
    """Chunked-prefill attention inside shard_map: two partials, one Update().

    ``q (B, C, Hq, D)`` / ``k_new``/``v_new (B, C, Hkv, D)`` / ``new_pos``/
    ``q_pos (B, C)``: the prompt chunk, replicated over the SP axes (the
    caller writes its K/V into the sharded cache *after* this call).
    ``k_cache``/``v_cache (B, S_loc, Hkv, D)`` / ``k_pos (B, S_loc)``: the
    resident cache shard holding every previous chunk.

    Partial 1 — chunk queries vs the resident cache (psum-merged across
    devices, same wire bytes as ``C`` decode tokens).  Partial 2 — chunk
    queries vs the chunk's own K/V, causal, computed redundantly on every
    device with zero communication.  Cross-chunk causality is their
    :func:`~repro.core.merge.merge_partials` combination.
    """
    res_out, res_lse = sp_decode_attention(
        q, k_cache, v_cache, k_pos, axis_names=axis_names, q_pos=q_pos,
        causal=True, window=window, scale=scale, impl=impl, block_k=block_k,
        return_lse=True,
    )
    blk_out, blk_lse = flash_attention(
        q, k_new, v_new, q_pos=q_pos, k_pos=new_pos, causal=True,
        window=window, scale=scale, impl=impl,
        block_q=min(block_q, max(q.shape[1], 1)), block_k=block_k,
    )
    out, lse = merge_partials(res_out, res_lse, blk_out, blk_lse)
    out, lse = finalize(out, lse)
    out = out.astype(q.dtype)
    return (out, lse) if return_lse else out


# ---------------------------------------------------------------------------
# cost models — the serving rows of the planner's arbitration table
# ---------------------------------------------------------------------------

# The psum/pmax payload is fp32 regardless of compute dtype: the merge
# accumulates in float32 (core/merge.py convention).
_MERGE_BYTES = 4


def decode_comm_cost(
    B, S, Hq, Hkv, D, P, *, bytes_per_elem=2, bidir_links=True, S_kv=None,
    table_pages=None, **_,
):
    """Resident-cache decode: one lse-weighted all-reduce of the partials.

    Payload per step: ``B * S * Hq * (D + 2)`` fp32 scalars (``S`` = query
    tokens per step, 1 in decode) — psum of num ``(D)`` + denom ``(1)`` and
    pmax of lse ``(1)``.  A bidirectional-ring all-reduce moves
    ``(P-1)/P x payload`` per device per direction.  Independent of the cache
    length ``S_kv`` — the whole point of keeping KV resident.

    ``table_pages`` prices the *paged* cache (``serving/kv_cache.py``): each
    step the per-slot block tables (``B * table_pages`` int32 entries) must be
    coherent on every device so each shard gathers its owned pages — priced
    conservatively as a per-step broadcast through the same ring (in practice
    tables change only at page granularity, so this is an upper bound).  The
    page *data* still never moves: paging changes where the resident cache
    lives, not what travels.
    """
    if P <= 1:
        return CommCost(0.0, 0.0)
    payload = B * S * Hq * (D + 2) * _MERGE_BYTES
    if table_pages:
        payload += B * table_pages * 4  # int32 block-table row broadcast
    per_dir = (P - 1) / P * payload
    return CommCost(per_dir, per_dir)


def prefill_comm_cost(
    B, S, Hq, Hkv, D, P, *, bytes_per_elem=2, bidir_links=True, S_kv=None,
    table_pages=None, **_,
):
    """Chunk-resident prefill: the decode psum evaluated at ``S`` chunk rows.

    Linear in the *query* rows only, so pricing a whole prompt is one
    evaluation at ``S = prompt_len`` (``n_chunks x`` the per-chunk cost).
    Ring/TokenRing in the same role re-circulate per chunk: their per-chunk
    cost scales with the *cache* length, i.e. ``O(S_kv)`` per chunk and
    ``O(S_kv^2 / chunk)`` per prompt — the gap ``bench_serving.py`` tabulates.

    The byte arithmetic IS the decode model (same psum, ``S`` query rows;
    ``table_pages`` adds the paged block-table broadcast term) — delegated so
    the two cannot drift apart.
    """
    return decode_comm_cost(
        B, S, Hq, Hkv, D, P, bytes_per_elem=bytes_per_elem,
        bidir_links=bidir_links, S_kv=S_kv, table_pages=table_pages,
    )


register_strategy(
    "decode",
    sp_decode_attention,
    comm_cost=decode_comm_cost,
    serving_side=True,
    kv_resident=True,
    auto_eligible=False,
    supports_window=True,
    extra_kwargs=frozenset({"table_pages"}),
    description="serving decode: replicated 1-token Q, resident sharded "
    "cache, lse-weighted psum merge",
)

register_strategy(
    "prefill",
    sp_prefill_chunk_attention,
    comm_cost=prefill_comm_cost,
    serving_side=True,
    kv_resident=True,
    auto_eligible=False,
    supports_window=True,
    extra_kwargs=frozenset({"table_pages"}),
    description="serving chunked prefill: replicated C-token chunk vs "
    "resident cache + local chunk block, merged via Update()",
)
