"""Pallas kernel + XLA flash vs the pure-jnp oracle: shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.zigzag import to_zigzag, zigzag_positions
from repro.kernels.flash_attention import PAD_POS
from repro.kernels.ops import flash_attention
from repro.kernels.ref import attention_reference


def _mk(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5
    )


SHAPES = [
    # B, Sq, Sk, Hq, Hkv, D
    (1, 128, 128, 1, 1, 64),
    (2, 256, 256, 4, 2, 64),
    (1, 128, 256, 4, 1, 128),  # cross lengths + MQA
    (1, 512, 512, 2, 2, 128),
]


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(impl, dtype, shape, causal):
    B, Sq, Sk, Hq, Hkv, D = shape
    rng = np.random.default_rng(hash((impl, str(dtype), shape, causal)) % 2**31)
    q = _mk(rng, (B, Sq, Hq, D), dtype)
    k = _mk(rng, (B, Sk, Hkv, D), dtype)
    v = _mk(rng, (B, Sk, Hkv, D), dtype)
    out, lse = flash_attention(
        q, k, v, causal=causal, impl=impl, block_q=128, block_k=128
    )
    ref_out, ref_lse = attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=causal,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out), **_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=5e-2 if dtype == jnp.bfloat16 else 1e-4, rtol=1e-3)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_flash_zigzag_positions(impl):
    """Kernel with zigzag global positions == reference on reordered data."""
    P = 4
    B, S, H, D = 1, 256, 2, 64
    rng = np.random.default_rng(0)
    q = _mk(rng, (B, S, H, D), jnp.float32)
    k = _mk(rng, (B, S, H, D), jnp.float32)
    v = _mk(rng, (B, S, H, D), jnp.float32)
    ref_out, _ = attention_reference(q, k, v, causal=True)

    qz, kz, vz = (to_zigzag(x, P, axis=1) for x in (q, k, v))
    pos = jnp.concatenate([zigzag_positions(S, P, j) for j in range(P)])
    out, _ = flash_attention(
        qz, kz, vz, q_pos=pos, k_pos=pos, causal=True, impl=impl,
        block_q=32, block_k=32,
    )
    ref_z = to_zigzag(ref_out, P, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_z), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_flash_sliding_window(impl):
    B, S, H, D = 1, 256, 2, 64
    rng = np.random.default_rng(1)
    q = _mk(rng, (B, S, H, D), jnp.float32)
    k = _mk(rng, (B, S, H, D), jnp.float32)
    v = _mk(rng, (B, S, H, D), jnp.float32)
    out, lse = flash_attention(
        q, k, v, causal=True, window=64, impl=impl, block_q=64, block_k=64
    )
    ref_out, ref_lse = attention_reference(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_flash_gradients_match_reference(impl):
    """custom_vjp blockwise backward == autodiff through the naive oracle."""
    B, S, Hq, Hkv, D = 1, 128, 4, 2, 32
    rng = np.random.default_rng(2)
    q = _mk(rng, (B, S, Hq, D), jnp.float32)
    k = _mk(rng, (B, S, Hkv, D), jnp.float32)
    v = _mk(rng, (B, S, Hkv, D), jnp.float32)
    w = _mk(rng, (B, S, Hq, D), jnp.float32)  # random cotangent projection

    def loss_flash(q, k, v):
        out, _ = flash_attention(q, k, v, causal=True, impl=impl, block_q=32, block_k=32)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        out, _ = attention_reference(q, k, v, causal=True)
        return jnp.sum(out * w)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_flash_empty_rows_safe_gradient(impl):
    """Fully-masked rows must not produce NaN grads."""
    B, S, H, D = 1, 64, 1, 16
    rng = np.random.default_rng(3)
    q = _mk(rng, (B, S, H, D), jnp.float32)
    k = _mk(rng, (B, S, H, D), jnp.float32)
    v = _mk(rng, (B, S, H, D), jnp.float32)
    q_pos = jnp.arange(S, dtype=jnp.int32)
    k_pos = jnp.arange(S, dtype=jnp.int32) + 1000  # all keys in the future

    def loss(q, k, v):
        out, _ = flash_attention(
            q, k, v, q_pos=q_pos, k_pos=k_pos, causal=True, impl=impl
        )
        return jnp.sum(out**2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert float(val) == 0.0
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


# ---------------------------------------------------------------------------
# Backward kernels (ISSUE 3 tentpole): Pallas dq + dk/dv vs the autodiff
# oracle, across the layouts the SP strategies actually feed them.
# ---------------------------------------------------------------------------

BWD_CASES = [
    # id, (B, S, Hq, Hkv, D), causal, layout, window
    ("causal", (1, 128, 2, 2, 32), True, "contig", None),
    ("noncausal", (1, 128, 2, 2, 32), False, "contig", None),
    ("gqa", (2, 128, 4, 2, 32), True, "contig", None),
    ("mqa", (1, 128, 4, 1, 64), True, "contig", None),
    ("zigzag", (1, 256, 2, 2, 32), True, "zigzag", None),
    ("zigzag_gqa", (1, 256, 4, 2, 32), True, "zigzag", None),
    ("window", (1, 256, 2, 2, 32), True, "contig", 64),
]


def _bwd_case_data(case_id, shape, layout):
    B, S, Hq, Hkv, D = shape
    # crc32, not hash(): stable across processes (PYTHONHASHSEED), so a CI
    # tolerance failure reproduces locally with the same data.
    import zlib

    rng = np.random.default_rng(zlib.crc32(repr((case_id, shape)).encode()))
    q = _mk(rng, (B, S, Hq, D), jnp.float32)
    k = _mk(rng, (B, S, Hkv, D), jnp.float32)
    v = _mk(rng, (B, S, Hkv, D), jnp.float32)
    w = _mk(rng, (B, S, Hq, D), jnp.float32)  # dout projection
    wl = _mk(rng, (B, S, Hq), jnp.float32)  # dlse projection
    if layout == "zigzag":
        P = 4
        q, k, v, w = (to_zigzag(x, P, axis=1) for x in (q, k, v, w))
        wl = to_zigzag(wl[..., None], P, axis=1)[..., 0]
        pos = jnp.concatenate([zigzag_positions(S, P, j) for j in range(P)])
    else:
        pos = jnp.arange(S, dtype=jnp.int32)
    return q, k, v, w, wl, pos


@pytest.mark.parametrize(
    "impl",
    [
        # The interpret-mode sweep is the acceptance gate but runs ~10x the
        # xla rows' time: slow-marked so CI's kernels-interpret job carries
        # it (plain `pytest` — the local tier-1 command — still runs all).
        pytest.param("pallas_interpret", marks=pytest.mark.slow),
        "xla",
    ],
)
@pytest.mark.parametrize("case", BWD_CASES, ids=[c[0] for c in BWD_CASES])
def test_flash_backward_matches_oracle(impl, case):
    """dq/dk/dv == jax.grad of the naive oracle to fp32 tolerance.

    The loss projects *both* outputs — out and lse — so the ``+ dlse``
    cotangent term TokenRing's partial merges rely on is exercised, not just
    the plain attention backward.
    """
    case_id, shape, causal, layout, window = case
    q, k, v, w, wl, pos = _bwd_case_data(case_id, shape, layout)

    def loss_flash(q, k, v):
        out, lse = flash_attention(
            q, k, v, q_pos=pos, k_pos=pos, causal=causal, window=window,
            impl=impl, block_q=64, block_k=64, block_q_bwd=32, block_k_bwd=32,
        )
        lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        return jnp.sum(out * w) + jnp.sum(lse * wl)

    def loss_ref(q, k, v):
        out, lse = attention_reference(
            q, k, v, causal=causal, window=window, q_pos=pos, k_pos=pos
        )
        lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        return jnp.sum(out * w) + jnp.sum(lse * wl)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4,
            err_msg=f"{case_id} d{nm}",
        )


@pytest.mark.slow
@pytest.mark.parametrize("blocks", [(32, 64), (64, 32), (128, 128)])
def test_flash_backward_interpret_matches_xla(blocks):
    """Same gradients from the Pallas kernels (interpret mode) and the tiled
    jnp backward, across asymmetric backward tile sizes."""
    bq, bk = blocks
    B, S, Hq, Hkv, D = 1, 256, 4, 2, 32
    q, k, v, w, wl, pos = _bwd_case_data("equiv", (B, S, Hq, Hkv, D), "zigzag")

    def make_loss(impl):
        def loss(q, k, v):
            out, lse = flash_attention(
                q, k, v, q_pos=pos, k_pos=pos, causal=True, impl=impl,
                block_q=64, block_k=64, block_q_bwd=bq, block_k_bwd=bk,
            )
            lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
            return jnp.sum(out * w) + jnp.sum(lse * wl)

        return loss

    g_i = jax.grad(make_loss("pallas_interpret"), argnums=(0, 1, 2))(q, k, v)
    g_x = jax.grad(make_loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_i, g_x, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5,
            err_msg=f"blocks={blocks} d{nm}",
        )


def test_backward_tile_skip_counts():
    """Zigzag-causal backward computes ~half the tiles of no-skip, and the
    window skip prunes further (the BENCH_kernels.json acceptance numbers)."""
    from repro.kernels.ops import backward_tile_counts

    S, P, blk = 2048, 4, 128
    pos = jnp.concatenate([zigzag_positions(S, P, j) for j in range(P)])[None]
    zz, total = backward_tile_counts(
        pos, pos, block_q=blk, block_k=blk, causal=True
    )
    full, _ = backward_tile_counts(
        pos, pos, block_q=blk, block_k=blk, causal=False
    )
    assert full == total == (S // blk) ** 2
    assert zz / full <= 0.6, (zz, full)
    # Tiles align with half-chunks here (blk divides S / 2P), so the skip is
    # exact: computed == the position-order lower triangle incl. diagonal.
    nq = S // blk
    assert zz == nq * (nq + 1) // 2
    win, _ = backward_tile_counts(
        jnp.arange(S)[None], jnp.arange(S)[None],
        block_q=blk, block_k=blk, causal=True, window=256,
    )
    assert win < zz  # window prunes deeper than causal alone


# ---------------------------------------------------------------------------
# Fused paged-decode kernel (ISSUE 10 tentpole): block-table indexing in the
# BlockSpec index maps vs the dense-gather path, both against the pure-jnp
# oracle on a manually materialized view.
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # id, page_size, (Hq, Hkv), lengths, window
    ("ps1_mha", 1, (2, 2), (1, 3), None),
    ("ps4_gqa", 4, (8, 2), (3, 4, 5), None),  # page-1 / exact / page+1
    ("ps8_mqa", 8, (4, 1), (8, 23), None),
    ("ps16_boundary", 16, (4, 4), (15, 16, 17, 64), None),
    ("ps8_window", 8, (4, 2), (40, 7), 16),
]


def _paged_case_data(case_id, ps, heads, lengths):
    """Paged pool state shaped like real serving state: per-slot pages
    assigned in *reversed* order (the indirection actually exercised), the
    table tail at the unmapped sentinel, and unwritten pool slots carrying
    random K/V under PAD_POS positions."""
    import zlib

    Hq, Hkv = heads
    B, D = len(lengths), 32
    W = max(-(-L // ps) for L in lengths) + 1  # every slot has a sentinel
    n_pages = sum(-(-L // ps) for L in lengths) + 2
    rng = np.random.default_rng(
        zlib.crc32(repr((case_id, ps, heads, tuple(lengths))).encode())
    )
    k_pool = rng.standard_normal((n_pages, ps, Hkv, D)).astype(np.float32)
    v_pool = rng.standard_normal((n_pages, ps, Hkv, D)).astype(np.float32)
    pos_pool = np.full((n_pages, ps), PAD_POS, np.int32)
    bt = np.full((B, W), n_pages, np.int32)
    free = list(range(n_pages))
    for b, L in enumerate(lengths):
        used = -(-L // ps)
        pages = [free.pop() for _ in range(used)][::-1]
        for ip, pg in enumerate(pages):
            bt[b, ip] = pg
            for off in range(ps):
                if ip * ps + off < L:
                    pos_pool[pg, off] = ip * ps + off
    q = rng.standard_normal((B, 1, Hq, D)).astype(np.float32)
    q_pos = (np.asarray(lengths, np.int32) - 1)[:, None]
    return (
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pos_pool), jnp.asarray(bt), jnp.asarray(q_pos),
    )


def _materialize_view(k_pool, v_pool, pos_pool, bt):
    """Dense per-row view via plain numpy indexing — the test's own gather,
    independent of the library's view_indices/gather_pages under test."""
    n_pages, ps = pos_pool.shape
    bt = np.asarray(bt)
    mapped = bt < n_pages
    safe = np.where(mapped, bt, 0)
    kv_shape = lambda pool: np.where(
        mapped[:, :, None, None, None], np.asarray(pool)[safe], 0.0
    )
    k = kv_shape(k_pool).reshape(bt.shape[0], -1, *k_pool.shape[2:])
    v = kv_shape(v_pool).reshape(bt.shape[0], -1, *v_pool.shape[2:])
    pos = np.where(mapped[:, :, None], np.asarray(pos_pool)[safe], PAD_POS)
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos.reshape(bt.shape[0], -1))


@pytest.mark.parametrize(
    "impl",
    [
        # Interpret mode is the kernel acceptance gate; CI's kernels-interpret
        # job carries it (slow mark), the xla rows gate the gather fallback
        # (and its lengths clamp) in tier-1.
        pytest.param("pallas_interpret", marks=pytest.mark.slow),
        "xla",
    ],
)
@pytest.mark.parametrize("case", PAGED_CASES, ids=[c[0] for c in PAGED_CASES])
def test_paged_decode_matches_oracle(impl, case):
    from repro.kernels.ops import paged_decode_attention

    case_id, ps, heads, lengths, window = case
    q, k_pool, v_pool, pos_pool, bt, q_pos = _paged_case_data(
        case_id, ps, heads, lengths
    )
    out, lse = paged_decode_attention(
        q, k_pool, v_pool, pos_pool, bt, q_pos,
        lengths=jnp.asarray(lengths, jnp.int32), window=window, impl=impl,
    )
    k_view, v_view, pos_view = _materialize_view(k_pool, v_pool, pos_pool, bt)
    ref_out, ref_lse = attention_reference(
        q, k_view, v_view, q_pos=q_pos, k_pos=pos_view, causal=True,
        window=window,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=2e-5,
        err_msg=f"{case_id} out",
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(ref_lse), atol=1e-4, rtol=1e-4,
        err_msg=f"{case_id} lse",
    )


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_paged_decode_dead_row_is_merge_identity(impl):
    """A slot with no mapped pages must come out as the TokenRing merge
    identity (out = 0, lse = -inf) — and the sentinel's clamped alias (the
    index maps prefetch pool page n_pages - 1) must never leak, even when
    that page holds another row's live, causally-visible data."""
    from repro.kernels.ops import paged_decode_attention

    q, k_pool, v_pool, pos_pool, bt, q_pos = _paged_case_data(
        "dead", 4, (4, 2), (9, 5)
    )
    n_pages = k_pool.shape[0]
    bt = bt.at[1, :].set(n_pages)  # row 1: fully unmapped
    # Make the clamp target page scream if it leaks: huge live-looking K/V
    # at positions row 1's query would consider visible.
    k_pool = k_pool.at[n_pages - 1].set(1e3)
    v_pool = v_pool.at[n_pages - 1].set(1e3)
    pos_pool = pos_pool.at[n_pages - 1].set(0)
    out, lse = paged_decode_attention(
        q, k_pool, v_pool, pos_pool, bt, q_pos,
        lengths=jnp.asarray([9, 0], jnp.int32), impl=impl,
    )
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    assert np.all(np.isneginf(np.asarray(lse[1])))
    assert np.all(np.isfinite(np.asarray(out[0]))), "live row unaffected"


def test_paged_decode_vmem_shapes_lintable():
    """kernel_buffer_shapes prices the paged kernel's blocks (Hkv x group
    queries against one whole page), and the analyze-gate lint set is clean
    at serving shape points."""
    from repro.analysis.kernel_lint import (
        lint_paged_decode_config,
        vmem_estimate,
    )

    est = vmem_estimate(
        "paged_decode", block_q=8, block_k=128, D=128, data_bytes=2,
        n_kv_heads=8,
    )
    assert 0 < est < 16 * 2**20
    assert est > vmem_estimate(
        "paged_decode", block_q=8, block_k=128, D=128, data_bytes=2
    )
    findings = lint_paged_decode_config(
        group=8, page_size=128, n_kv_heads=8, n_pages=64, table_width=8,
        D=128, data_bytes=2, subject="t",
    )
    assert findings == []


def test_paged_sentinel_lint_catches_mutant():
    """The KERN-PAGED-SENTINEL lint must flag a predicate that decides
    liveness from page contents instead of the raw table entry."""
    from repro.analysis.kernel_lint import paged_sentinel_findings

    def mutant_skip(entry, k_pos, q_pos, *, n_pages, window=None):
        # drops the entry term: trusts the (aliased) positions
        return jnp.min(k_pos) >= PAD_POS // 2

    findings = paged_sentinel_findings(
        n_pages=8, page_size=4, subject="mutant", skip_fn=mutant_skip
    )
    assert {f.rule for f in findings} == {"KERN-PAGED-SENTINEL"}
    assert len(findings) == 2  # sentinel and corrupt entry both attended


def test_pick_block_boundary():
    """_pick_block: degrade gracefully to a dividing power of two >= the
    sublane granule, but refuse the silent collapse to near-per-row tiles."""
    from repro.kernels.ops import _pick_block

    assert _pick_block(1024, 512) == 512
    assert _pick_block(1536, 512) == 512  # 3 * 512 (whisper enc_seq)
    assert _pick_block(24, 16) == 8  # halves until it divides
    assert _pick_block(1, 512) == 1  # decode: Sq=1 is the "s itself" case
    assert _pick_block(384, 512) == 384  # s <= target: s itself
    assert _pick_block(8, 4) == 4  # explicit small target honored as-is
    for s, t in [(1023, 512), (1026, 512), (1028, 512), (6, 4)]:
        # odd / 2*odd / 4*odd above target: best tile is sub-granule
        with pytest.raises(ValueError, match="no power-of-two tile"):
            _pick_block(s, t)
    # ... and the public entry point surfaces it for untileable sequences
    rng = np.random.default_rng(5)
    x = _mk(rng, (1, 1026, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="no power-of-two tile"):
        flash_attention(x, x, x, causal=True, impl="xla", block_q=64, block_k=64)
