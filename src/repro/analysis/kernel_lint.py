"""Static Pallas kernel-config lints: VMEM footprint, grid coverage,
tile-skip soundness, and the shared divisibility preconditions.

Nothing here compiles or interprets a kernel.  The VMEM estimate prices the
exact BlockSpec/scratch shapes the kernels declare
(``kernels.flash_attention.kernel_buffer_shapes``); the tile-skip check
evaluates the kernels' *own* ``tile_skip`` predicate on concrete position
tiles and cross-examines it against exhaustive per-element visibility — a
skipped tile containing one visible (query, key) pair is attention mass
silently dropped (KERN-LIVE-SKIP).

VMEM model: the Mosaic pipeline double-buffers every in/out block (fetch of
grid step ``i+1`` overlaps compute of ``i``), scratch accumulators are
single-buffered:

    footprint = 2 * (in_blocks + out_blocks) + scratch

against a ~16 MiB per-core budget (:data:`VMEM_BUDGET_BYTES`).
"""

from __future__ import annotations

import numpy as np

from repro.analysis.preconditions import check_tile_divisible, finding
from repro.analysis.report import Finding

__all__ = [
    "VMEM_BUDGET_BYTES",
    "vmem_estimate",
    "vmem_findings",
    "grid_findings",
    "tile_skip_findings",
    "lint_flash_config",
    "paged_vmem_findings",
    "paged_bounds_findings",
    "paged_sentinel_findings",
    "lint_paged_decode_config",
]

# Per-core VMEM on current TPU generations (the budget pallas kernels must
# fit refs + scratch into; see the accelerator guide).
VMEM_BUDGET_BYTES = 16 * 2**20

_KINDS = ("fwd", "bwd_dq", "bwd_dkv")


def _elem_bytes(elem: str, data_bytes: int) -> int:
    return {"data": data_bytes, "f32": 4, "i32": 4}[elem]


def vmem_estimate(
    kind: str, *, block_q: int, block_k: int, D: int, data_bytes: int,
    n_kv_heads: int = 1,
) -> int:
    """Estimated VMEM bytes of one kernel's per-grid-step working set."""
    from repro.kernels.flash_attention import kernel_buffer_shapes

    shapes = kernel_buffer_shapes(
        kind, block_q=block_q, block_k=block_k, D=D, n_kv_heads=n_kv_heads
    )
    pipelined = sum(
        int(np.prod(shape)) * _elem_bytes(elem, data_bytes)
        for part in ("in", "out")
        for shape, elem in shapes[part]
    )
    scratch = sum(
        int(np.prod(shape)) * _elem_bytes(elem, data_bytes)
        for shape, elem in shapes["scratch"]
    )
    return 2 * pipelined + scratch


def vmem_findings(
    cfg,
    *,
    D: int,
    data_bytes: int,
    subject: str,
    budget: int = VMEM_BUDGET_BYTES,
):
    """KERN-VMEM findings for a ``FlashConfig``'s fwd + bwd kernels."""
    findings: list[Finding] = []
    blocks = {
        "fwd": (cfg.block_q, cfg.block_k),
        "bwd_dq": (cfg.bwd_block_q, cfg.bwd_block_k),
        "bwd_dkv": (cfg.bwd_block_q, cfg.bwd_block_k),
    }
    for kind in _KINDS:
        bq, bk = blocks[kind]
        est = vmem_estimate(
            kind, block_q=bq, block_k=bk, D=D, data_bytes=data_bytes
        )
        if est > budget:
            findings.append(
                Finding(
                    "KERN-VMEM",
                    subject,
                    f"{kind} kernel at block_q={bq}, block_k={bk}, D={D}, "
                    f"{data_bytes}-byte data needs ~{est / 2**20:.1f} MiB "
                    f"VMEM (budget {budget / 2**20:.0f} MiB)",
                )
            )
    return findings


def grid_findings(
    Sq: int, Sk: int, *, block_q: int, block_k: int, subject: str
):
    """KERN-GRID-COVER: the grid must tile each sequence exactly once."""
    findings: list[Finding] = []
    for axis, S, b in (("q", Sq, block_q), ("kv", Sk, block_k)):
        blk = min(b, S)
        if blk <= 0 or S % blk:
            findings.append(
                Finding(
                    "KERN-GRID-COVER",
                    subject,
                    f"{axis} axis: {S} rows do not tile into {blk}-row "
                    f"blocks ({S} % {blk} = {S % blk if blk else S}) — some "
                    f"rows would be computed twice or never",
                )
            )
    return findings


def tile_skip_findings(
    q_pos,
    k_pos,
    *,
    block_q: int,
    block_k: int,
    causal: bool,
    window: int | None,
    subject: str,
    skip_fn=None,
):
    """KERN-LIVE-SKIP: the skip predicate must never kill a live tile.

    ``q_pos``/``k_pos`` are concrete ``(B, S)`` position layouts (contig,
    zigzag, ring-rotated...).  ``skip_fn(q_pos_tile, k_pos_tile, causal=...,
    window=...)`` defaults to the kernels' own ``tile_skip``; it is
    injectable so mutation tests can prove the lint catches a corrupted
    predicate.  Visibility is checked exhaustively per element with the
    kernels' ``tile_mask`` — the ground truth the predicate must respect.
    """
    import jax.numpy as jnp

    from repro.kernels.flash_attention import tile_mask, tile_skip

    if skip_fn is None:
        skip_fn = tile_skip
    q_pos = np.asarray(q_pos)
    k_pos = np.asarray(k_pos)
    B, Sq = q_pos.shape
    Sk = k_pos.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    findings: list[Finding] = []
    if Sq % bq or Sk % bk:
        return findings  # grid_findings owns this defect
    for b in range(B):
        for iq in range(Sq // bq):
            qp = jnp.asarray(q_pos[b, iq * bq:(iq + 1) * bq])
            for ik in range(Sk // bk):
                kp = jnp.asarray(k_pos[b, ik * bk:(ik + 1) * bk])
                skip = bool(skip_fn(qp, kp, causal=causal, window=window))
                if not skip:
                    continue
                visible = bool(
                    jnp.any(tile_mask(qp, kp, causal=causal, window=window))
                )
                if visible:
                    findings.append(
                        Finding(
                            "KERN-LIVE-SKIP",
                            subject,
                            f"batch {b}, q-tile {iq}, kv-tile {ik} "
                            f"(block_q={bq}, block_k={bk}, causal={causal}, "
                            f"window={window}): predicate skips a tile with "
                            f"visible (query, key) pairs",
                        )
                    )
    return findings


def paged_vmem_findings(
    *,
    group: int,
    page_size: int,
    n_kv_heads: int,
    D: int,
    data_bytes: int,
    subject: str,
    budget: int = VMEM_BUDGET_BYTES,
):
    """KERN-VMEM for the fused paged-decode kernel.

    Its per-grid-step working set streams every KV head's GQA query group
    against one whole pool page — ``block_q`` maps to the group width,
    ``block_k`` to the page size — and the scratch is the
    ``(Hkv, group, D)`` float32 accumulator plus two lane-replicated
    ``(Hkv, group, MXU_LANE)`` m/l rows.
    """
    est = vmem_estimate(
        "paged_decode", block_q=group, block_k=page_size, D=D,
        data_bytes=data_bytes, n_kv_heads=n_kv_heads,
    )
    if est <= budget:
        return []
    return [
        Finding(
            "KERN-VMEM",
            subject,
            f"paged_decode kernel at group={group}, page_size={page_size}, "
            f"Hkv={n_kv_heads}, D={D}, {data_bytes}-byte data needs "
            f"~{est / 2**20:.1f} MiB VMEM (budget {budget / 2**20:.0f} MiB)",
        )
    ]


def paged_bounds_findings(block_tables, *, n_pages: int, subject: str):
    """KERN-PAGED-BOUNDS: every prefetch address the kernel's own index-map
    clamp produces must land inside the pool.

    The BlockSpec index maps address the page pool straight from the
    scalar-prefetched block table; an out-of-pool index is an out-of-bounds
    DMA.  This evaluates ``page_index_clamp`` — the exact function the index
    maps call — over a concrete table that includes the unmapped sentinel
    (``n_pages``) and any corrupt entries the caller wants to probe.
    """
    import jax.numpy as jnp

    from repro.kernels.paged_attention import page_index_clamp

    bt = np.asarray(block_tables)
    clamped = np.asarray(page_index_clamp(jnp.asarray(bt), n_pages))
    bad = (clamped < 0) | (clamped >= n_pages)
    findings: list[Finding] = []
    if bad.any():
        rows, cols = np.nonzero(bad)
        b, w = int(rows[0]), int(cols[0])
        findings.append(
            Finding(
                "KERN-PAGED-BOUNDS",
                subject,
                f"index-map clamp maps table entry {int(bt[b, w])} (slot "
                f"{b}, page {w}) to pool index {int(clamped[b, w])} outside "
                f"[0, {n_pages}) — out-of-bounds page prefetch "
                f"({int(bad.sum())} offending entries)",
            )
        )
    return findings


def paged_sentinel_findings(
    *,
    n_pages: int,
    page_size: int,
    window: int | None = None,
    subject: str,
    skip_fn=None,
):
    """KERN-PAGED-SENTINEL: the paged skip predicate must be decided by the
    raw table entry, never by the aliased page's contents.

    The index maps clamp the sentinel onto a *real* pool page, so when the
    kernel body runs, an unmapped entry's ``k_pos`` ref holds some other
    request's perfectly live positions.  The predicate therefore must (a)
    skip any ``entry >= n_pages`` even against fully-visible positions —
    sentinel and corrupt alike — and (b) never skip a mapped page that has
    visible keys (the KERN-LIVE-SKIP dual: attention mass silently dropped).
    ``skip_fn`` defaults to the kernel's own ``page_skip`` and is injectable
    so mutation tests can prove the lint catches a corrupted predicate.
    """
    import jax.numpy as jnp

    from repro.kernels.paged_attention import page_mask, page_skip

    if skip_fn is None:
        skip_fn = page_skip
    findings: list[Finding] = []
    # A page of fully-written, causally-visible positions, queried from just
    # past its end — the worst case for an aliased sentinel.
    live_pos = jnp.arange(page_size, dtype=jnp.int32)
    q_pos = jnp.int32(page_size)
    assert bool(jnp.any(page_mask(live_pos, q_pos, window=window))), (
        "lint self-check: probe page must be visible"
    )
    for entry in (n_pages, n_pages + 7):  # sentinel, corrupt
        skip = bool(
            skip_fn(
                jnp.int32(entry), live_pos, q_pos,
                n_pages=n_pages, window=window,
            )
        )
        if not skip:
            findings.append(
                Finding(
                    "KERN-PAGED-SENTINEL",
                    subject,
                    f"unmapped table entry {entry} (n_pages={n_pages}) is "
                    f"not skipped against live aliased positions — the "
                    f"kernel would attend another request's page",
                )
            )
    skip = bool(
        skip_fn(
            jnp.int32(0), live_pos, q_pos, n_pages=n_pages, window=window
        )
    )
    if skip:
        findings.append(
            Finding(
                "KERN-PAGED-SENTINEL",
                subject,
                f"mapped page 0 with visible keys (q_pos={int(q_pos)}, "
                f"window={window}) is skipped — attention mass silently "
                f"dropped",
            )
        )
    return findings


def lint_paged_decode_config(
    *,
    group: int,
    page_size: int,
    n_kv_heads: int,
    n_pages: int,
    table_width: int,
    D: int,
    data_bytes: int,
    window: int | None = None,
    subject: str,
):
    """All paged-decode kernel lints at one shape point.

    The bounds probe uses a table shaped like real serving state: pages
    assigned in descending order (the indirection actually exercised), the
    tail unmapped at the sentinel, plus one deliberately corrupt entry.
    """
    findings = paged_vmem_findings(
        group=group, page_size=page_size, n_kv_heads=n_kv_heads, D=D,
        data_bytes=data_bytes, subject=subject,
    )
    bt = np.full((1, table_width), n_pages, np.int32)
    used = min(table_width, n_pages)
    bt[0, :used] = np.arange(n_pages - used, n_pages, dtype=np.int32)[::-1]
    if table_width > 1:
        bt[0, table_width - 1] = n_pages + 13  # corrupt entry
    findings += paged_bounds_findings(bt, n_pages=n_pages, subject=subject)
    findings += paged_sentinel_findings(
        n_pages=n_pages, page_size=page_size, window=window, subject=subject
    )
    return findings


def lint_flash_config(
    cfg,
    *,
    Sq: int,
    Sk: int,
    D: int,
    data_bytes: int,
    q_pos=None,
    k_pos=None,
    subject: str,
):
    """All kernel lints for one ``FlashConfig`` at one shape point."""
    findings = vmem_findings(
        cfg, D=D, data_bytes=data_bytes, subject=subject
    )
    for bq, bk in {(cfg.block_q, cfg.block_k),
                   (cfg.bwd_block_q, cfg.bwd_block_k)}:
        findings += grid_findings(
            Sq, Sk, block_q=bq, block_k=bk, subject=subject
        )
        findings += finding(
            "PRE-TILE-DIV", subject, check_tile_divisible(Sq, bq)
        )
        findings += finding(
            "PRE-TILE-DIV", subject, check_tile_divisible(Sk, bk)
        )
    if q_pos is not None and k_pos is not None and not findings:
        findings += tile_skip_findings(
            q_pos, k_pos, block_q=cfg.block_q, block_k=cfg.block_k,
            causal=cfg.causal, window=cfg.window, subject=subject,
        )
    return findings
