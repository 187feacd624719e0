"""Fused paged-decode attention Pallas kernel.

The serving hot path: one new query token per request attends to that
request's whole paged KV history.  The dense fallback first *gathers* the
block-table view into a contiguous ``(B, V, Hkv, Dh)`` buffer and then runs
flash attention over it — the copy is pure HBM bandwidth overhead, linear in
context length.  This kernel removes the gather entirely: the per-slot block
table is **scalar-prefetched** into SMEM, and the K/V/pos BlockSpec *index
maps* read it to address the page pool directly, so the Mosaic pipeline
streams exactly the pages a request maps — no dense view ever exists.

Design (mirrors the PR-3 kernel family in ``flash_attention.py``):
  * Grid is ``(B, W)`` with ``W`` the block-table width (logical pages per
    slot); the page dimension is sequential (``arbitrary``) so the
    online-softmax state for one request lives in VMEM scratch across
    consecutive pages.  One grid step takes one whole physical page,
    ``(page_size, Hkv, D)`` — pages are non-contiguous in the pool, so a
    BlockSpec block cannot span more than one, and a block that kept the
    pool's trailing ``(Hkv, D)`` dims whole is the one the TPU compiler
    accepts (a per-head ``(1, page_size, 1, D)`` block is refused: its last
    two dims are neither (8, 128)-aligned nor the full array dims).  The body
    loops over the page's KV heads.
  * GQA/MQA: each KV head's whole query-head *group* is streamed through
    the accumulators at once — the q/out blocks are ``(Hkv, group, D)`` and
    the scratch is ``(Hkv, group, D)`` (+ two ``(Hkv, group, MXU_LANE)``
    lane-replicated m/l rows), so each page is fetched once per request,
    never per query head.
  * Unmapped block-table entries carry the sentinel ``n_pages``.  The index
    maps *clamp* the page id so the prefetch address stays in-bounds, while
    the kernel body reads the **raw** table entry and skips the whole step
    via ``pl.when`` when ``page >= n_pages`` — the clamped page may hold
    some other request's live data, so masking must never rely on its
    contents.
  * Beyond-used-length positions need no length input: the pos pool carries
    ``PAD_POS`` in every unwritten slot, and the same ``_tile_skip``-style
    predicate / per-element mask as the PR-3 kernels drops them (plus the
    causal ``q_pos >= k_pos`` and sliding-window terms).
  * Emits ``(out, lse)`` — partials compatible with ``core/merge.Update()``
    and ``core.decode.psum_merge_partials``, so under a mesh each shard runs
    the kernel over its local pages and the ring merge combines shards.
    Rows whose every page is dead come out as ``out = 0, lse = -inf`` (the
    merge identity).

Bytes per decode token (per layer): the kernel reads only mapped pages —
``pages_used * page_size * Hkv * Dh * 2 dtypes`` — where the gather path
writes *and* re-reads the full ``W * page_size`` logical view regardless of
how many pages are actually used.  ``benchmarks/roofline_report.py`` prints
the two side by side.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import MXU_LANE, NEG_INF, PAD_POS

__all__ = [
    "paged_decode_fwd_pallas",
    "page_index_clamp",
    "page_skip",
    "page_mask",
]


def page_index_clamp(entry, n_pages: int):
    """Page id the BlockSpec index maps hand the Mosaic pipeline.

    Clamping (rather than wrapping or passing through) keeps the sentinel's
    prefetch address inside the pool for *any* ``entry >= n_pages``, corrupt
    tables included; the kernel body drops the step from the raw entry.
    ``analysis.kernel_lint.paged_bounds_findings`` cross-examines this.
    """
    return jnp.minimum(entry, n_pages - 1)


def page_skip(entry, k_pos, q_pos, *, n_pages: int, window: int | None = None):
    """Tile-level skip predicate — the paged analogue of flash's
    ``tile_skip`` with the extra unmapped-sentinel term.

    Liveness is decided from the raw table ``entry``, never from the page's
    positions: a clamped sentinel aliases some other request's live page, so
    its ``k_pos`` may look valid.  OR-ing the sentinel term first keeps the
    predicate safe even though ``k_pos`` is garbage for unmapped entries.
    A step is dead when the entry is unmapped, every slot is padding, every
    key is causally after the query, or every key fell out of the window.
    """
    k_min = jnp.min(k_pos)
    skip = entry >= n_pages
    skip = jnp.logical_or(skip, k_min >= PAD_POS // 2)
    skip = jnp.logical_or(skip, q_pos < k_min)
    if window is not None:
        skip = jnp.logical_or(skip, jnp.max(k_pos) <= q_pos - window)
    return skip


def page_mask(k_pos, q_pos, *, window: int | None = None):
    """Per-element key visibility within one page: padding (``PAD_POS``
    covers both unwritten slots and beyond-used-length), causal, window."""
    mask = k_pos < PAD_POS // 2
    mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _paged_decode_kernel(
    # scalar-prefetch refs (SMEM) — also fed to the BlockSpec index maps
    bt_ref,  # (B, W)  int32  block tables; sentinel == n_pages means unmapped
    qp_ref,  # (B, 1)  int32  query position (== used length) per request
    # pipelined VMEM refs
    q_ref,  # (Hkv, group, D) q.dtype — every KV head's query group
    k_ref,  # (page_size, Hkv, D)     — one whole physical pool page
    v_ref,  # (page_size, Hkv, D)
    pos_ref,  # (1, page_size) int32  — that page's global token positions
    out_ref,  # (Hkv, group, D)
    lse_ref,  # (Hkv, group, MXU_LANE) float32 (lane-replicated)
    acc_ref,  # VMEM scratch (Hkv, group, D) float32
    m_ref,  # VMEM scratch (Hkv, group, MXU_LANE) float32 (lane-replicated)
    l_ref,  # VMEM scratch (Hkv, group, MXU_LANE) float32
    *,
    n_pages: int,
    window: int | None,
    scale: float,
    num_pages_grid: int,
):
    b = pl.program_id(0)
    ip = pl.program_id(1)
    n_kv_heads = k_ref.shape[1]

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Raw table entry — NOT the clamped one the index maps used.  A clamped
    # sentinel aliases a real pool page, so correctness requires deciding
    # liveness from the table itself, never from the aliased page's positions
    # (page_skip owns that invariant; the lint mutation-tests it).
    page = bt_ref[b, ip]
    q_pos = qp_ref[b, 0]
    k_pos = pos_ref[0, :]  # (page_size,)
    skip = page_skip(page, k_pos, q_pos, n_pages=n_pages, window=window)

    @pl.when(jnp.logical_not(skip))
    def _compute():
        # Per-element mask; every query row of every group shares the single
        # decode position.
        mask = page_mask(k_pos, q_pos, window=window)[None, :]
        for h in range(n_kv_heads):  # static unroll over the page's KV heads
            q = q_ref[h].astype(jnp.float32) * scale  # (group, D)
            k = k_ref[:, h, :].astype(jnp.float32)  # (page_size, D)
            v = v_ref[:, h, :].astype(jnp.float32)  # (page_size, D)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # (group, page_size)
            scores = jnp.where(mask, scores, NEG_INF)

            m_prev = m_ref[h][:, 0]  # (group,)
            l_prev = l_ref[h][:, 0]
            m_cur = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            p = jnp.exp(scores - safe_m[:, None])  # (group, page_size)
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(jnp.minimum(m_prev - safe_m, 0.0))
            alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

            l_new = alpha * l_prev + jnp.sum(p, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_ref[h] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])

    @pl.when(ip == num_pages_grid - 1)
    def _finalize():
        m = m_ref[...]
        l = l_ref[...]
        valid = l > 0.0
        denom = jnp.where(valid, l, 1.0)
        out = acc_ref[...] / denom[..., :1]
        out = jnp.where(valid[..., :1], out, 0.0)
        out_ref[...] = out.astype(out_ref.dtype)
        lse_ref[...] = jnp.where(valid, m + jnp.log(denom), -jnp.inf)


def paged_decode_fwd_pallas(
    q,
    k_pool,
    v_pool,
    pos_pool,
    block_tables,
    q_pos,
    *,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool = False,
):
    """Fused paged decode attention — no materialized KV gather.

    Shapes: ``q (B, 1, Hq, D)`` (one decode token per request),
    ``k_pool/v_pool (n_pages, page_size, Hkv, D)``,
    ``pos_pool (n_pages, page_size) int32`` (``PAD_POS`` in unwritten slots),
    ``block_tables (B, W) int32`` (entry ``>= n_pages`` == unmapped sentinel),
    ``q_pos (B, 1) int32``.  Returns ``(out, lse)`` with ``out (B, 1, Hq, D)``
    in q.dtype and ``lse (B, 1, Hq)`` float32 — mergeable TokenRing partials
    (all-dead rows give ``out = 0, lse = -inf``, the merge identity).
    """
    B, Sq, Hq, D = q.shape
    assert Sq == 1, f"paged decode kernel is single-token (Sq={Sq})"
    n_pages, page_size, Hkv, Dk = k_pool.shape
    assert Dk == D and v_pool.shape == k_pool.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    W = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (D**0.5)

    bt = block_tables.astype(jnp.int32)
    qp = q_pos.astype(jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel,
        n_pages=n_pages,
        window=window,
        scale=float(scale),
        num_pages_grid=W,
    )

    # Index maps address the pool through the scalar-prefetched table.  The
    # clamp keeps the sentinel's prefetch in-bounds; the kernel body skips it
    # from the raw entry (see _paged_decode_kernel).
    def _page_map(b, ip, bt_ref, qp_ref):
        return (page_index_clamp(bt_ref[b, ip], n_pages), 0, 0, 0)

    def _pos_map(b, ip, bt_ref, qp_ref):
        return (page_index_clamp(bt_ref[b, ip], n_pages), 0, 0)

    heads = (None, Hkv, group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, q_pos
        grid=(B, W),
        in_specs=[
            pl.BlockSpec(heads + (D,), lambda b, ip, *_: (b, 0, 0, 0)),
            pl.BlockSpec((None, page_size, Hkv, D), _page_map),
            pl.BlockSpec((None, page_size, Hkv, D), _page_map),
            pl.BlockSpec((None, 1, page_size), _pos_map),
        ],
        out_specs=[
            pl.BlockSpec(heads + (D,), lambda b, ip, *_: (b, 0, 0, 0)),
            pl.BlockSpec(heads + (MXU_LANE,), lambda b, ip, *_: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, D), jnp.float32),
            pltpu.VMEM((Hkv, group, MXU_LANE), jnp.float32),
            pltpu.VMEM((Hkv, group, MXU_LANE), jnp.float32),
        ],
    )

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, group, MXU_LANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode",
    )(
        bt, qp, q.reshape(B, Hkv, group, D), k_pool, v_pool,
        pos_pool.reshape(n_pages, 1, page_size),
    )
    return out.reshape(B, 1, Hq, D), lse[..., 0].reshape(B, 1, Hq)
