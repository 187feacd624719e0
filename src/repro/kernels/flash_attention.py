"""Flash-attention forward + backward Pallas TPU kernels.

The forward is the per-device block compute of every TokenRing /
Ring-Attention step (the paper's ``Attention(Q_j^i, K_j, V_j)`` producing
``block_out, block_lse``).  The backward is the matching pair of blockwise
recompute kernels that make *training* under TokenRing live at kernel speed
— ~2/3 of a training step's attention FLOPs are in here.

TPU-native design decisions (vs the CUDA FlashAttention-2 the paper calls):
  * Tiling is expressed through ``BlockSpec``s: HBM->VMEM movement is done by
    the Mosaic pipeline, not hand-rolled ``cp.async`` as on GPU.
  * The kernels work head-major: q/k/v are ``(B, H, S, D)`` so every block
    is ``(None, None, block, D)`` — a squeezed batch and head, and a
    ``(block, D)`` tile whose last two dims satisfy Mosaic's (8, 128) rule.
    Positions enter as ``(B, 1, S)`` and the per-row lse/delta/dlse as
    ``(B, H, 1, S)``, so their blocks are ``(1, block)`` rows (a ``(1, bq, 1)``
    block on a token-major ``(B, S, H)`` array is refused by the TPU
    compiler).  The ``ops`` wrappers keep the public ``(B, S, H, D)`` layout
    and transpose in and out.
  * Forward grid is ``(B, Hq, num_q_blocks, num_kv_blocks)`` with the KV
    dimension marked ``arbitrary`` (sequential): the online-softmax state for
    one (b, h, q-block) lives in VMEM scratch across consecutive KV-grid
    steps — the TPU analogue of a CUDA thread-block's register accumulator.
  * ``(block_q, MXU_LANE)`` shaped running max / denominator scratch keeps the
    state layout lane-aligned (8x128 tiles), matching MXU-friendly shapes.
  * Masking is *position-based*: the kernel receives the global token position
    of every query/key row, so contiguous, zigzag (causal load-balanced) and
    ring-rotated layouts all use the same kernel.  Fully-masked tiles are
    skipped via ``pl.when`` (this is what makes zigzag-causal cost ~half of
    full-matrix attention instead of just masking it).

The backward is split into two kernels (FlashAttention-2 style — no atomics,
no cross-program reductions):
  * **dq kernel** — grid ``(B, Hq, num_q_blocks, num_kv_blocks)``, KV
    sequential; ``dq`` accumulates in VMEM scratch across KV steps.
  * **dk/dv kernel** — grid ``(B, Hkv, num_kv_blocks, group, num_q_blocks)``,
    the (group, q-block) tail sequential; ``dk``/``dv`` accumulate in VMEM
    scratch.  The GQA group sum happens through the *index maps* (query head
    ``h_kv * group + g`` streams through the same accumulator) — KV-head
    gradients never materialize ``Hq``-sized repeats.

Both backward kernels carry the ``+ dlse`` cotangent term: TokenRing
circulates ``(out, lse)`` partials and merges them downstream, so the lse
output is *used* and its cotangent must flow into ``ds`` (see
``docs/kernels.md`` for the derivation).  Both share the forward's
position-based tile skip, so the zigzag-causal backward computes ~half the
tiles of a full matrix.

GQA is handled in the index maps (KV head = query head // group) so KV blocks
are fetched once per query-head group without materializing repeats.

Forward returns ``(out, lse)`` — the partials TokenRing circulates.

Validated against ``ref.py`` (forward) and ``jax.grad`` of the oracle
(backward) in interpret mode (CPU) across shape/dtype sweeps in
``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "flash_attention_fwd_pallas",
    "flash_attention_bwd_pallas",
    "kernel_buffer_shapes",
    "tile_skip",
    "tile_mask",
    "PAD_POS",
    "MXU_LANE",
]

NEG_INF = float(jnp.finfo(jnp.float32).min)
# Sentinel position for padded KV rows; anything >= PAD_POS/2 is masked out.
PAD_POS = 2**30
MXU_LANE = 128


def _tile_skip(q_pos, k_pos, *, causal: bool, window: int | None):
    """Whether a (q-tile, kv-tile) score block is provably all-masked.

    Position-based, so it is exact for contiguous, zigzag, and ring-rotated
    layouts alike: a tile is dead when every key is padding, every key is
    causally after every query, or every key is left of every query's window.
    Shared by the forward kernel, both backward kernels, and the XLA
    backward's block skip (`ops.backward_tile_counts` evaluates the same
    predicate to report skip ratios).
    """
    k_min = jnp.min(k_pos)
    all_pad = k_min >= PAD_POS // 2
    skip = all_pad
    if causal:
        skip = jnp.logical_or(jnp.max(q_pos) < k_min, skip)
    if window is not None:
        skip = jnp.logical_or(skip, jnp.max(k_pos) <= jnp.min(q_pos) - window)
    return skip


def _tile_mask(q_pos, k_pos, *, causal: bool, window: int | None):
    """(bq, bk) visibility mask for one score tile (padding/causal/window)."""
    mask = k_pos[None, :] < PAD_POS // 2
    if causal:
        mask = jnp.logical_and(mask, q_pos[:, None] >= k_pos[None, :])
    if window is not None:
        mask = jnp.logical_and(mask, q_pos[:, None] - k_pos[None, :] < window)
    return mask


# Public names for the tile predicates: the static kernel lint
# (``repro.analysis.kernel_lint``) evaluates the *same* functions on concrete
# position tiles, so "the analyzer's skip math" and "the kernel's skip math"
# cannot drift apart.
tile_skip = _tile_skip
tile_mask = _tile_mask


def kernel_buffer_shapes(
    kind: str, *, block_q: int, block_k: int, D: int, n_kv_heads: int = 1
):
    """Per-grid-step VMEM buffer shapes of one kernel, for footprint lints.

    ``kind`` is ``"fwd"``, ``"bwd_dq"``, ``"bwd_dkv"`` or ``"paged_decode"``.
    Returns ``{"in": [...], "out": [...], "scratch": [...]}`` where each entry
    is ``(shape, elem)`` with ``elem`` one of ``"data"`` (the q/k/v dtype),
    ``"f32"`` or ``"i32"``.  Squeezed (``None``) block dims are left out.
    These mirror the BlockSpecs and scratch_shapes of the ``pallas_call``s
    below and in ``paged_attention.py`` — update both together.  For
    ``"paged_decode"``, ``block_q`` is the GQA query-head group, ``block_k``
    the page size, and ``n_kv_heads`` the KV heads of the one whole pool page
    each sequential grid step takes.
    """
    bq, bk = block_q, block_k
    if kind == "paged_decode":
        hg = (n_kv_heads, bq)
        return {
            "in": [(hg + (D,), "data"), ((bk, n_kv_heads, D), "data"),
                   ((bk, n_kv_heads, D), "data"), ((1, bk), "i32")],
            "out": [(hg + (D,), "data"), (hg + (MXU_LANE,), "f32")],
            "scratch": [(hg + (D,), "f32"), (hg + (MXU_LANE,), "f32"),
                        (hg + (MXU_LANE,), "f32")],
        }
    pos = [((1, bq), "i32"), ((1, bk), "i32")]
    qkv = [((bq, D), "data"), ((bk, D), "data"), ((bk, D), "data")]
    if kind == "fwd":
        return {
            "in": pos + qkv,
            "out": [((bq, D), "data"), ((1, bq), "f32")],
            "scratch": [((bq, D), "f32"), ((bq, MXU_LANE), "f32"),
                        ((bq, MXU_LANE), "f32")],
        }
    rows = [((1, bq), "f32")] * 3  # lse, delta, dlse
    bwd_in = pos + qkv + [((bq, D), "data")] + rows  # + dout
    if kind == "bwd_dq":
        return {
            "in": bwd_in,
            "out": [((bq, D), "f32")],
            "scratch": [((bq, D), "f32")],
        }
    if kind == "bwd_dkv":
        return {
            "in": bwd_in,
            "out": [((bk, D), "f32")] * 2,
            "scratch": [((bk, D), "f32")] * 2,
        }
    raise ValueError(f"unknown kernel kind {kind!r}")


def _fwd_kernel(
    # per-batch position arrays are regular VMEM refs here (see BlockSpecs)
    q_pos_ref,  # (1, block_q)      int32  global positions of this q tile
    k_pos_ref,  # (1, block_k)      int32  global positions of this kv tile
    q_ref,  # (block_q, D) in q.dtype
    k_ref,  # (block_k, D)
    v_ref,  # (block_k, D)
    out_ref,  # (block_q, D)
    lse_ref,  # (1, block_q)       float32
    acc_ref,  # VMEM scratch (block_q, D)        float32
    m_ref,  # VMEM scratch (block_q, MXU_LANE) float32 (lane-replicated)
    l_ref,  # VMEM scratch (block_q, MXU_LANE) float32
    *,
    causal: bool,
    window: int | None,
    scale: float,
    num_kv_blocks: int,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_pos_ref[0, :]  # (bq,)
    k_pos = k_pos_ref[0, :]  # (bk,)

    # Tile-level skip: under causal masking a tile whose every key position is
    # later than every query position (or is padding) contributes nothing.
    skip = _tile_skip(q_pos, k_pos, causal=causal, window=window)

    @pl.when(jnp.logical_not(skip))
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[...].astype(jnp.float32)  # (bk, D)
        v = v_ref[...].astype(jnp.float32)  # (bk, D)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)

        mask = _tile_mask(q_pos, k_pos, causal=causal, window=window)
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[:, 0]  # (bq,)
        l_prev = l_ref[:, 0]  # (bq,)
        m_cur = jnp.max(scores, axis=-1)  # (bq,)
        m_new = jnp.maximum(m_prev, m_cur)
        # Rows still fully masked keep m_new == NEG_INF; make exp() produce 0
        # without generating inf-inf NaNs.
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(scores - safe_m[:, None])  # (bq, bk)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(jnp.minimum(m_prev - safe_m, 0.0))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        valid = l > 0.0
        denom = jnp.where(valid, l, 1.0)
        out = acc_ref[...] / denom[:, None]
        out = jnp.where(valid[:, None], out, 0.0)
        out_ref[...] = out.astype(out_ref.dtype)
        lse = jnp.where(valid, m + jnp.log(denom), -jnp.inf)
        lse_ref[0, :] = lse


def flash_attention_fwd_pallas(
    q,
    k,
    v,
    q_pos,
    k_pos,
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Pallas flash-attention forward, head-major.

    Shapes: ``q (B,Hq,Sq,D)``, ``k/v (B,Hkv,Sk,D)``, ``q_pos (B,Sq) int32``,
    ``k_pos (B,Sk) int32`` (per-batch positions enable continuous-batching
    decode).  ``Sq % block_q == 0`` and ``Sk % block_k == 0`` must hold (the
    ``ops`` wrapper picks dividing blocks).  Returns ``(out, lse)`` with
    ``out (B,Hq,Sq,D)`` in q.dtype and ``lse (B,Hq,Sq)`` float32.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dk = k.shape
    assert Dk == D and v.shape == k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    if scale is None:
        scale = 1.0 / (D**0.5)

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        window=window,
        scale=float(scale),
        num_kv_blocks=nk,
    )

    grid = (B, Hq, nq, nk)
    out_shape = [
        jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32),
    ]
    q_spec = pl.BlockSpec((None, None, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, D), lambda b, h, iq, ik: (b, h // group, ik, 0)
    )
    in_specs = [
        pl.BlockSpec((None, 1, block_q), lambda b, h, iq, ik: (b, 0, iq)),  # q_pos
        pl.BlockSpec((None, 1, block_k), lambda b, h, iq, ik: (b, 0, ik)),  # k_pos
        q_spec,
        kv_spec,  # k
        kv_spec,  # v
    ]
    out_specs = [
        q_spec,
        pl.BlockSpec((None, None, 1, block_q), lambda b, h, iq, ik: (b, h, 0, iq)),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, D), jnp.float32),
        pltpu.VMEM((block_q, MXU_LANE), jnp.float32),
        pltpu.VMEM((block_q, MXU_LANE), jnp.float32),
    ]

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )
    out, lse = call(q_pos.reshape(B, 1, Sq), k_pos.reshape(B, 1, Sk), q, k, v)
    return out, lse.reshape(B, Hq, Sq)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
#
# Flash backward recompute, per score tile s = (q @ k^T) * scale:
#     p  = exp(s - lse)                       (true probabilities, no rescan)
#     dv = p^T @ dout
#     dp = dout @ v^T
#     ds = p * (dp - delta + dlse) * scale,   delta = rowsum(dout * out)
#     dq = ds @ k,   dk = ds^T @ q
# The ``+ dlse`` term is TokenRing-specific: the lse output feeds downstream
# online-softmax merges, so d(lse)/d(s) = p contributes p * dlse to ds.


def _bwd_p_ds(q, k, v, dout, lse, delta, dlse, q_pos, k_pos, *,
              causal, window, scale):
    """Shared tile recompute: returns ``(p, ds)`` for one (bq, bk) tile.

    All inputs are float32 2-D tiles; ``lse``/``delta``/``dlse`` are (bq,)
    rows.  Fully-masked rows carry ``lse = -inf`` -> the safe substitution
    makes every masked p exactly 0 (scores are NEG_INF there), so no explicit
    row_valid select is needed.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)
    mask = _tile_mask(q_pos, k_pos, causal=causal, window=window)
    s = jnp.where(mask, s, NEG_INF)
    lse_safe = jnp.where(jnp.isneginf(lse), 0.0, lse)
    p = jnp.exp(s - lse_safe[:, None])  # masked entries: exp(NEG_INF) == 0
    p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        dout, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)
    dlse_safe = jnp.where(jnp.isneginf(lse), 0.0, dlse)
    ds = p * (dp - delta[:, None] + dlse_safe[:, None]) * scale
    return p, ds


def _bwd_dq_kernel(
    q_pos_ref,  # (1, block_q) int32
    k_pos_ref,  # (1, block_k) int32
    q_ref,  # (block_q, D)
    k_ref,  # (block_k, D)   KV head = query head // group
    v_ref,  # (block_k, D)
    dout_ref,  # (block_q, D)
    lse_ref,  # (1, block_q) float32
    delta_ref,  # (1, block_q) float32  rowsum(dout * out)
    dlse_ref,  # (1, block_q) float32
    dq_ref,  # (block_q, D) float32 out
    dq_acc_ref,  # VMEM scratch (block_q, D) float32
    *,
    causal: bool,
    window: int | None,
    scale: float,
    num_kv_blocks: int,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    q_pos = q_pos_ref[0, :]
    k_pos = k_pos_ref[0, :]
    skip = _tile_skip(q_pos, k_pos, causal=causal, window=window)

    @pl.when(jnp.logical_not(skip))
    def _compute():
        k = k_ref[...].astype(jnp.float32)
        _, ds = _bwd_p_ds(
            q_ref[...].astype(jnp.float32), k, v_ref[...].astype(jnp.float32),
            dout_ref[...].astype(jnp.float32), lse_ref[0, :], delta_ref[0, :],
            dlse_ref[0, :], q_pos, k_pos, causal=causal, window=window,
            scale=scale,
        )
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        dq_ref[...] = dq_acc_ref[...]


def _bwd_dkv_kernel(
    q_pos_ref,  # (1, block_q) int32
    k_pos_ref,  # (1, block_k) int32
    q_ref,  # (block_q, D)   query head = h_kv * group + g
    k_ref,  # (block_k, D)
    v_ref,  # (block_k, D)
    dout_ref,  # (block_q, D)
    lse_ref,  # (1, block_q) float32
    delta_ref,  # (1, block_q) float32
    dlse_ref,  # (1, block_q) float32
    dk_ref,  # (block_k, D) float32 out
    dv_ref,  # (block_k, D) float32 out
    dk_acc_ref,  # VMEM scratch (block_k, D) float32
    dv_acc_ref,  # VMEM scratch (block_k, D) float32
    *,
    causal: bool,
    window: int | None,
    scale: float,
    group: int,
    num_q_blocks: int,
):
    g = pl.program_id(3)
    iq = pl.program_id(4)
    # Sequential index over the (group, q-block) tail: the dk/dv accumulators
    # live across all of it — this is where the GQA group sum happens, with
    # the index maps streaming each group head's Q through the same scratch.
    inner = g * num_q_blocks + iq

    @pl.when(inner == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    q_pos = q_pos_ref[0, :]
    k_pos = k_pos_ref[0, :]
    skip = _tile_skip(q_pos, k_pos, causal=causal, window=window)

    @pl.when(jnp.logical_not(skip))
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        dout = dout_ref[...].astype(jnp.float32)
        p, ds = _bwd_p_ds(
            q, k_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32),
            dout, lse_ref[0, :], delta_ref[0, :], dlse_ref[0, :], q_pos,
            k_pos, causal=causal, window=window, scale=scale,
        )
        dv_acc_ref[...] += jax.lax.dot_general(
            p, dout, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # p^T @ dout: (bk, D)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # ds^T @ q: (bk, D)

    @pl.when(inner == group * num_q_blocks - 1)
    def _finalize():
        dk_ref[...] = dk_acc_ref[...]
        dv_ref[...] = dv_acc_ref[...]


def flash_attention_bwd_pallas(
    q,
    k,
    v,
    q_pos,
    k_pos,
    out,
    lse,
    dout,
    dlse,
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Pallas flash-attention backward: returns ``(dq, dk, dv)`` in float32.

    Head-major like the forward (``q/out/dout (B,Hq,Sq,D)``,
    ``k/v (B,Hkv,Sk,D)``, ``lse/dlse (B,Hq,Sq)``); ``out``/``lse`` are the
    forward products (residuals), ``dout``/``dlse`` the cotangents.  Two
    pallas_calls: the dq grid parallelizes over ``(B, Hq, q_blocks)`` with
    KV sequential; the dk/dv grid parallelizes over ``(B, Hkv, kv_blocks)``
    with ``(group, q_blocks)`` sequential so the GQA group sum stays in VMEM
    scratch.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dk = k.shape
    assert Dk == D and v.shape == k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    if scale is None:
        scale = 1.0 / (D**0.5)

    # Per-row operands as (B, Hq, 1, Sq): their blocks are (1, block_q) rows.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    rows = [x.astype(jnp.float32).reshape(B, Hq, 1, Sq) for x in (lse, delta, dlse)]
    q_pos = q_pos.reshape(B, 1, Sq)
    k_pos = k_pos.reshape(B, 1, Sk)

    row_spec = pl.BlockSpec((None, None, 1, block_q), lambda b, h, iq, ik: (b, h, 0, iq))
    q_spec = pl.BlockSpec((None, None, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, D), lambda b, h, iq, ik: (b, h // group, ik, 0)
    )
    dq_call = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, window=window, scale=float(scale),
            num_kv_blocks=nk,
        ),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, 1, block_q), lambda b, h, iq, ik: (b, 0, iq)),  # q_pos
            pl.BlockSpec((None, 1, block_k), lambda b, h, iq, ik: (b, 0, ik)),  # k_pos
            q_spec,  # q
            kv_spec,  # k
            kv_spec,  # v
            q_spec,  # dout
            row_spec,  # lse
            row_spec,  # delta
            row_spec,  # dlse
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )
    dq = dq_call(q_pos, k_pos, q, k, v, dout, *rows)

    # dk/dv: query head streamed through the accumulator is h*group + g.
    qrow_spec = pl.BlockSpec(
        (None, None, 1, block_q), lambda b, h, ik, g, iq: (b, h * group + g, 0, iq)
    )
    qhead_spec = pl.BlockSpec(
        (None, None, block_q, D), lambda b, h, ik, g, iq: (b, h * group + g, iq, 0)
    )
    kv_spec = pl.BlockSpec(
        (None, None, block_k, D), lambda b, h, ik, g, iq: (b, h, ik, 0)
    )
    dkv_call = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, window=window, scale=float(scale),
            group=group, num_q_blocks=nq,
        ),
        grid=(B, Hkv, nk, group, nq),
        in_specs=[
            pl.BlockSpec((None, 1, block_q), lambda b, h, ik, g, iq: (b, 0, iq)),  # q_pos
            pl.BlockSpec((None, 1, block_k), lambda b, h, ik, g, iq: (b, 0, ik)),  # k_pos
            qhead_spec,  # q
            kv_spec,  # k
            kv_spec,  # v
            qhead_spec,  # dout
            qrow_spec,  # lse
            qrow_spec,  # delta
            qrow_spec,  # dlse
        ],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, Sk, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary", "arbitrary",
            ),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )
    dk, dv = dkv_call(q_pos, k_pos, q, k, v, dout, *rows)
    return dq, dk, dv
