"""Jitted public wrapper around the flash-attention kernels.

``flash_attention`` dispatches between:
  * ``impl="pallas"``            — the Pallas TPU kernels (real hardware),
  * ``impl="pallas_interpret"``  — same kernel bodies, interpreted on CPU
                                   (used by the correctness tests),
  * ``impl="xla"``               — a scan-over-blocks pure-jnp flash
                                   (O(block) memory, used for CPU runs and for
                                   the 512-device dry-run compile where Mosaic
                                   isn't available),
  * ``impl="auto"``              — pallas on TPU, xla elsewhere.

All impls return the TokenRing partials ``(out, lse)`` and share one
``custom_vjp``.  The backward is a blockwise recompute (flash-style, no
O(S^2) residuals) carrying the ``+ dlse`` cotangent term TokenRing's partial
merges require; on the pallas impls it runs as the two Pallas kernels in
``flash_attention.py`` (dq; dk/dv with the GQA group summed in VMEM scratch),
on xla as a tiled jnp double-scan.  Every backward path skips provably
all-masked tiles — the same position predicate the forward uses — so
zigzag-causal training costs ~half of full-matrix (`backward_tile_counts`
reports the exact ratio).  Backward tile sizes default to the forward's and
are tunable separately via ``block_q_bwd`` / ``block_k_bwd``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import (
    PAD_POS,
    flash_attention_bwd_pallas,
    flash_attention_fwd_pallas,
)
from repro.kernels.ref import normalize_positions

__all__ = [
    "flash_attention",
    "paged_decode_attention",
    "FlashConfig",
    "backward_tile_counts",
]

NEG_INF = float(jnp.finfo(jnp.float32).min)


@dataclass(frozen=True)
class FlashConfig:
    causal: bool = False
    window: int | None = None
    scale: float | None = None
    block_q: int = 512
    block_k: int = 512
    # Backward tile sizes; None inherits the forward's.  The backward holds
    # more live tiles per step (q, k, v, dout + two accumulators), so smaller
    # blocks can be the right VMEM trade on real hardware.
    block_q_bwd: int | None = None
    block_k_bwd: int | None = None
    # Decode-path KV tile; None inherits block_k.  The fused paged kernel's
    # intrinsic KV tile is the page size, so this knob tunes the decode-time
    # dense/gather (xla oracle) flash calls.
    block_k_decode: int | None = None
    impl: str = "auto"  # auto | pallas | pallas_interpret | xla

    def resolve_impl(self) -> str:
        if self.impl != "auto":
            return self.impl
        return "pallas" if jax.default_backend() == "tpu" else "xla"

    @property
    def bwd_block_q(self) -> int:
        return self.block_q_bwd if self.block_q_bwd is not None else self.block_q

    @property
    def bwd_block_k(self) -> int:
        return self.block_k_bwd if self.block_k_bwd is not None else self.block_k

    @property
    def decode_block_k(self) -> int:
        return (
            self.block_k_decode if self.block_k_decode is not None else self.block_k
        )


def _pick_block(s: int, target: int) -> int:
    """Largest power-of-two block <= target dividing s (s itself if small).

    Raises when a sequence that *needs* tiling (``s > target``) only admits
    sub-sublane tiles (< 8 rows, e.g. ``s = 2 * odd``): silently degrading to
    near-per-row grid steps is a perf cliff, not a fallback.  The selection
    and the error message live in ``analysis.preconditions`` so the static
    linter (PRE-TILE-DIV) and this runtime check can never drift apart.
    """
    from repro.analysis.preconditions import pick_block

    return pick_block(s, target)


# ---------------------------------------------------------------------------
# XLA (pure jnp) flash forward: scan over KV blocks, O(block) memory.
# ---------------------------------------------------------------------------


def _xla_flash_fwd(cfg: FlashConfig, q, k, v, q_pos, k_pos):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv
    scale = cfg.scale if cfg.scale is not None else 1.0 / (D**0.5)
    bk = _pick_block(Sk, cfg.block_k)
    nk = Sk // bk

    qf = q.astype(jnp.float32) * scale  # (B,Sq,Hq,D)
    # reshape kv into blocks: (nk, B, bk, Hkv, D)
    kb = jnp.moveaxis(k.reshape(B, nk, bk, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, Hkv, D), 1, 0)
    kpb = jnp.moveaxis(k_pos.reshape(B, nk, bk), 1, 0)  # (nk, B, bk)

    acc0 = jnp.zeros((B, Hq, Sq, D), jnp.float32)
    m0 = jnp.full((B, Hq, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)

    def step(carry, blk):
        acc, m, l = carry
        kb_, vb_, kp_ = blk
        if group > 1:
            kb_ = jnp.repeat(kb_, group, axis=2)
            vb_ = jnp.repeat(vb_, group, axis=2)
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, kb_.astype(jnp.float32)
        )  # (B,Hq,Sq,bk)
        mask = kp_[:, None, :] < PAD_POS // 2  # (B, 1, bk)
        mask = jnp.broadcast_to(mask, (B, Sq, kp_.shape[-1]))
        if cfg.causal:
            mask = jnp.logical_and(mask, q_pos[:, :, None] >= kp_[:, None, :])
        if cfg.window is not None:
            mask = jnp.logical_and(
                mask, q_pos[:, :, None] - kp_[:, None, :] < cfg.window
            )
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(mask[:, None], p, 0.0)
        alpha = jnp.exp(jnp.minimum(m - safe_m, 0.0))
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb_.astype(jnp.float32)
        )
        return (acc_new, m_new, l_new), None

    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kb, vb, kpb))
    valid = l > 0.0
    out = acc / jnp.where(valid, l, 1.0)[..., None]
    out = jnp.where(valid[..., None], out, 0.0)
    lse = jnp.where(valid, m + jnp.log(jnp.where(valid, l, 1.0)), -jnp.inf)
    # (B,Hq,Sq,*) -> (B,Sq,Hq,*)
    return out.transpose(0, 2, 1, 3).astype(q.dtype), lse.transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# Blockwise backward (flash-style recompute).
# ---------------------------------------------------------------------------


def _tile_skip_grid(q_pos, k_pos, bq, bk, *, causal, window):
    """Per-(batch, q-tile, kv-tile) dead-tile predicate, ``(B, nq, nk)`` bool.

    The vectorized form of the kernels' per-program ``_tile_skip``: a tile is
    dead when every key is padding, causally after every query, or left of
    every query's window.  Used by the XLA backward's block skip and by
    :func:`backward_tile_counts`.
    """
    B, Sq = q_pos.shape
    Sk = k_pos.shape[1]
    nq, nk = Sq // bq, Sk // bk
    qp = q_pos.reshape(B, nq, bq)
    kp = k_pos.reshape(B, nk, bk)
    q_max = jnp.max(qp, axis=-1)  # (B, nq)
    k_min = jnp.min(kp, axis=-1)  # (B, nk)
    skip = jnp.broadcast_to((k_min >= PAD_POS // 2)[:, None, :], (B, nq, nk))
    if causal:
        skip = jnp.logical_or(skip, q_max[:, :, None] < k_min[:, None, :])
    if window is not None:
        q_min = jnp.min(qp, axis=-1)
        k_max = jnp.max(kp, axis=-1)
        skip = jnp.logical_or(
            skip, k_max[:, None, :] <= q_min[:, :, None] - window
        )
    return skip


def backward_tile_counts(
    q_pos,
    k_pos,
    *,
    block_q: int,
    block_k: int,
    causal: bool = False,
    window: int | None = None,
):
    """``(computed, total)`` backward score tiles for a position layout.

    Counts per (batch, q-tile, kv-tile) — exactly the predicate each Pallas
    backward program evaluates, so ``computed / total`` is the kernel's true
    block-compute fraction (zigzag-causal lands near ``(1 + 1/nq) / 2``).
    The XLA backward skips a tile only when it is dead for *every* batch row
    (its ``lax.cond`` needs one scalar), so its skip count can be slightly
    more conservative under per-request position layouts.
    """
    B, Sq = q_pos.shape
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(k_pos.shape[1], block_k)
    skip = _tile_skip_grid(q_pos, k_pos, bq, bk, causal=causal, window=window)
    total = int(np.prod(skip.shape))
    computed = total - int(jnp.sum(skip))
    return computed, total


def _xla_flash_bwd(cfg: FlashConfig, q, k, v, q_pos, k_pos, out, lse, dout, dlse):
    """Tiled jnp backward: KV-block scan x Q-block scan, dead tiles skipped.

    Mirrors the Pallas kernels' block structure (same recompute, same
    ``+ dlse`` term, same skip predicate) so CPU/XLA training gets the same
    ~2x zigzag-causal saving — ``lax.cond`` executes only the taken branch.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv
    scale = cfg.scale if cfg.scale is not None else 1.0 / (D**0.5)
    bq = _pick_block(Sq, cfg.bwd_block_q)
    bk = _pick_block(Sk, cfg.bwd_block_k)
    nq, nk = Sq // bq, Sk // bk

    qf = q.astype(jnp.float32)
    doutf = dout.astype(jnp.float32)
    # delta = rowsum(dout * out): (B,Sq,Hq)
    delta = jnp.sum(doutf * out.astype(jnp.float32), axis=-1)
    # The lse output participates in downstream online-softmax merges (that is
    # the whole point of TokenRing partials), so its cotangent must flow:
    # d lse / d scores = p  =>  ds gains a "+ dlse" term alongside (dp - delta).
    row_valid = jnp.logical_not(jnp.isneginf(lse))
    dlse = jnp.where(row_valid, dlse.astype(jnp.float32), 0.0)
    # Safe lse for exp(): fully-masked rows have lse=-inf and p ends up 0.
    lse_safe = jnp.where(row_valid, lse, 0.0)

    def q_tiles(x):
        # (B, Sq, ...) -> (nq, B, bq, ...)
        return jnp.moveaxis(x.reshape((B, nq, bq) + x.shape[2:]), 1, 0)

    qb = q_tiles(qf)  # (nq,B,bq,Hq,D)
    dob = q_tiles(doutf)
    qpb = q_tiles(q_pos)  # (nq,B,bq)
    lseb = q_tiles(lse_safe)  # (nq,B,bq,Hq)
    deltab = q_tiles(delta)
    dlseb = q_tiles(dlse)

    kb = jnp.moveaxis(k.reshape(B, nk, bk, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, Hkv, D), 1, 0)
    kpb = jnp.moveaxis(k_pos.reshape(B, nk, bk), 1, 0)  # (nk, B, bk)

    # One evaluation of the kernels' skip predicate for the whole grid,
    # batch-reduced to the scalar lax.cond needs (a tile runs unless it is
    # dead for *every* batch row), threaded through the scans as xs.
    skip_grid = jnp.moveaxis(
        jnp.all(
            _tile_skip_grid(
                q_pos, k_pos, bq, bk, causal=cfg.causal, window=cfg.window
            ),
            axis=0,
        ),
        1, 0,
    )  # (nk, nq)

    def kv_step(dq_acc, kv_blk):
        kb_, vb_, kp_, skip_col = kv_blk
        if group > 1:
            kbx = jnp.repeat(kb_, group, axis=2).astype(jnp.float32)
            vbx = jnp.repeat(vb_, group, axis=2).astype(jnp.float32)
        else:
            kbx = kb_.astype(jnp.float32)
            vbx = vb_.astype(jnp.float32)

        def q_step(carry, q_blk):
            dk_acc, dv_acc = carry
            qb_, dob_, qp_, lse_, delta_, dlse_, skip = q_blk

            def compute(_):
                s = jnp.einsum("bqhd,bkhd->bhqk", qb_, kbx) * scale
                mask = kp_[:, None, :] < PAD_POS // 2  # (B, 1, bk)
                mask = jnp.broadcast_to(mask, (B, bq, bk))
                if cfg.causal:
                    mask = jnp.logical_and(
                        mask, qp_[:, :, None] >= kp_[:, None, :]
                    )
                if cfg.window is not None:
                    mask = jnp.logical_and(
                        mask, qp_[:, :, None] - kp_[:, None, :] < cfg.window
                    )
                s = jnp.where(mask[:, None], s, NEG_INF)
                # p: true softmax probabilities recovered from lse.
                p = jnp.exp(s - lse_.transpose(0, 2, 1)[..., None])
                p = jnp.where(mask[:, None], p, 0.0)
                dp = jnp.einsum("bqhd,bkhd->bhqk", dob_, vbx)
                ds = (
                    p
                    * (
                        dp
                        - delta_.transpose(0, 2, 1)[..., None]
                        + dlse_.transpose(0, 2, 1)[..., None]
                    )
                    * scale
                )  # (B,Hq,bq,bk)
                dq_t = jnp.einsum("bhqk,bkhd->bqhd", ds, kbx)
                dk_full = jnp.einsum("bhqk,bqhd->bkhd", ds, qb_)
                dv_full = jnp.einsum("bhqk,bqhd->bkhd", p, dob_)
                if group > 1:
                    dk_t = dk_full.reshape(B, bk, Hkv, group, D).sum(axis=3)
                    dv_t = dv_full.reshape(B, bk, Hkv, group, D).sum(axis=3)
                else:
                    dk_t, dv_t = dk_full, dv_full
                return dq_t, dk_t, dv_t

            def skipped(_):
                return (
                    jnp.zeros((B, bq, Hq, D), jnp.float32),
                    jnp.zeros((B, bk, Hkv, D), jnp.float32),
                    jnp.zeros((B, bk, Hkv, D), jnp.float32),
                )

            dq_t, dk_t, dv_t = jax.lax.cond(skip, skipped, compute, None)
            return (dk_acc + dk_t, dv_acc + dv_t), dq_t

        (dk_, dv_), dq_tiles_ = jax.lax.scan(
            q_step,
            (
                jnp.zeros((B, bk, Hkv, D), jnp.float32),
                jnp.zeros((B, bk, Hkv, D), jnp.float32),
            ),
            (qb, dob, qpb, lseb, deltab, dlseb, skip_col),
        )
        return dq_acc + dq_tiles_, (dk_, dv_)

    dq0 = jnp.zeros((nq, B, bq, Hq, D), jnp.float32)
    dq_tiled, (dks, dvs) = jax.lax.scan(kv_step, dq0, (kb, vb, kpb, skip_grid))
    dq = jnp.moveaxis(dq_tiled, 0, 1).reshape(B, Sq, Hq, D)
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, Hkv, D)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, Hkv, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _heads_major(x):
    """``(B, S, H, D) <-> (B, H, S, D)``: the Pallas kernels run head-major
    (their blocks must be ``(S-tile, D)``), the public API is token-major.
    Each call moves O(S*H*D) bytes through HBM."""
    return x.transpose(0, 2, 1, 3)


def _flash_bwd(cfg: FlashConfig, q, k, v, q_pos, k_pos, out, lse, dout, dlse):
    impl = cfg.resolve_impl()
    if impl in ("pallas", "pallas_interpret"):
        Sq, Sk = q.shape[1], k.shape[1]
        dq, dk, dv = flash_attention_bwd_pallas(
            *map(_heads_major, (q, k, v)), q_pos, k_pos,
            _heads_major(out), lse.transpose(0, 2, 1), _heads_major(dout),
            dlse.transpose(0, 2, 1),
            causal=cfg.causal, window=cfg.window, scale=cfg.scale,
            block_q=_pick_block(Sq, cfg.bwd_block_q),
            block_k=_pick_block(Sk, cfg.bwd_block_k),
            interpret=impl == "pallas_interpret",
        )
        return tuple(
            _heads_major(g).astype(x.dtype) for g, x in ((dq, q), (dk, k), (dv, v))
        )
    return _xla_flash_bwd(cfg, q, k, v, q_pos, k_pos, out, lse, dout, dlse)


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: FlashConfig, q, k, v, q_pos, k_pos):
    impl = cfg.resolve_impl()
    if impl == "xla":
        return _xla_flash_fwd(cfg, q, k, v, q_pos, k_pos)
    Sq, Sk = q.shape[1], k.shape[1]
    out, lse = flash_attention_fwd_pallas(
        *map(_heads_major, (q, k, v)),
        q_pos,
        k_pos,
        causal=cfg.causal,
        window=cfg.window,
        scale=cfg.scale,
        block_q=_pick_block(Sq, cfg.block_q),
        block_k=_pick_block(Sk, cfg.block_k),
        interpret=impl == "pallas_interpret",
    )
    return _heads_major(out), lse.transpose(0, 2, 1)


def _flash_fwd_rule(cfg, q, k, v, q_pos, k_pos):
    out, lse = _flash(cfg, q, k, v, q_pos, k_pos)
    return (out, lse), (q, k, v, q_pos, k_pos, out, lse)


def _flash_bwd_rule(cfg, res, cts):
    q, k, v, q_pos, k_pos, out, lse = res
    dout, dlse = cts
    dq, dk, dv = _flash_bwd(cfg, q, k, v, q_pos, k_pos, out, lse, dout, dlse)
    zero_pos_q = np.zeros(q_pos.shape, dtype=jax.dtypes.float0)
    zero_pos_k = np.zeros(k_pos.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zero_pos_q, zero_pos_k


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q,
    k,
    v,
    *,
    q_pos=None,
    k_pos=None,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    block_q_bwd: int | None = None,
    block_k_bwd: int | None = None,
    impl: str = "auto",
):
    """Public flash attention returning TokenRing partials ``(out, lse)``.

    See module docstring for impl choices.  ``q_pos``/``k_pos`` default to
    ``arange`` (contiguous layout).  ``block_q_bwd``/``block_k_bwd`` tune the
    backward tiles independently (None inherits the forward's).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    q_pos = normalize_positions(q_pos, B, Sq)
    k_pos = normalize_positions(k_pos, B, Sk)
    cfg = FlashConfig(
        causal=causal,
        window=window,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        block_q_bwd=block_q_bwd,
        block_k_bwd=block_k_bwd,
        impl=impl,
    )
    return _flash(cfg, q, k, v, q_pos, k_pos)


def paged_decode_attention(
    q,
    k_pool,
    v_pool,
    pos_pool,
    block_tables,
    q_pos,
    layer,
    *,
    lengths=None,
    window: int | None = None,
    scale: float | None = None,
    block_k: int | None = None,
    impl: str = "auto",
):
    """Paged decode attention over layer ``layer`` of a stacked page-pool KV
    cache -> ``(out, lse)``.

    Dispatches on ``impl`` exactly like :func:`flash_attention`:

      * ``pallas`` / ``pallas_interpret`` — the fused kernel in
        ``paged_attention.py``: the block table is scalar-prefetched and the
        BlockSpec index maps address the page pool directly, so **no gathered
        dense buffer ever exists**.
      * ``xla`` — the oracle: materialize the block-table view with
        ``gather_pages`` (clamped to pages actually mapped when ``lengths``
        is given) and run the jnp flash over it.
      * ``auto`` — pallas on TPU, xla elsewhere.

    The kernel reads the layer where it lies (its index maps take
    ``layer``); the oracle gathers its view from that layer.

    Shapes: ``q (B, 1, Hq, D)``, pools ``(L, n_pages, page_size, Hkv, D)``,
    ``layer`` an int32 scalar,
    ``pos_pool (n_pages, page_size) int32``, ``block_tables (B, W) int32``
    (entries ``>= n_pages`` are the unmapped sentinel), ``q_pos (B, 1)``,
    ``lengths (B,)`` used lengths (xla view clamp only — the kernel masks by
    the pos pool's PAD sentinel and needs no lengths).  ``block_k`` tunes the
    xla oracle's KV tile; the fused kernel's tile is intrinsically the page
    size.  Decode is forward-only: no vjp, partials merge downstream.
    """
    resolved = FlashConfig(impl=impl).resolve_impl()
    if resolved in ("pallas", "pallas_interpret"):
        from repro.kernels.paged_attention import paged_decode_fwd_pallas

        return paged_decode_fwd_pallas(
            q, k_pool, v_pool, pos_pool, block_tables, q_pos, layer,
            window=window, scale=scale,
            interpret=resolved == "pallas_interpret",
        )
    if resolved != "xla":
        raise ValueError(f"unknown impl {impl!r}")
    # function-level import: serving.kv_cache is a consumer of this module's
    # siblings, keep the layering one-directional at import time.
    from repro.serving.kv_cache import gather_pages, gather_positions, view_indices

    page_size = k_pool.shape[2]
    flat_view = view_indices(block_tables, page_size, lengths=lengths)
    k_view = gather_pages(k_pool, flat_view, layer)
    v_view = gather_pages(v_pool, flat_view, layer)
    pos_view = gather_positions(pos_pool, flat_view)
    return flash_attention(
        q, k_view, v_view, q_pos=q_pos, k_pos=pos_view,
        causal=True, window=window, scale=scale,
        block_q=1, block_k=block_k if block_k is not None else 512,
        impl="xla",
    )
