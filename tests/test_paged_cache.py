"""Paged sequence-parallel KV cache: allocator, paged-vs-dense equivalence,
and the engine's page-pool boundaries (admission, growth, preemption,
capacity retirement).

The numerical contract: a paged read gathers the block-table view and runs
the *same* SP attention as the dense slab, so paged logits equal dense
logits bit-for-bit up to fp noise — across page sizes and with deliberately
non-contiguous page assignments.  The scheduling contract: admission waits
for pages (strict FCFS), decode grows page-granularly, a dry pool preempts
the newest request (which resumes *exactly*, re-prefilled from its retained
prompt + generated tokens), and retirement happens at the last writable
position — never past it.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.api import ParallelContext
from repro.models import build_model
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import (
    PageAllocator,
    PageAllocatorError,
    PrefixIndex,
    pages_for,
)

from test_serving import GREEDY_TOL, _legacy_step, assert_greedy_chain_matches, donated

PCTX = ParallelContext(mesh=None, impl="xla")


def _setup():
    cfg = ARCHS["qwen3-1.7b"].reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_head=32, d_ff=128,
        vocab_size=97,
    )
    bundle = build_model(cfg, PCTX)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_page_allocator_alloc_free_high_water():
    a = PageAllocator(4)
    assert a.free_pages == 4 and a.pages_in_use == 0
    p1 = a.alloc(3)
    assert len(set(p1)) == 3 and a.free_pages == 1 and a.high_water == 3
    with pytest.raises(MemoryError):
        a.alloc(2)
    assert a.free_pages == 1, "failed alloc must not leak pages"
    a.free(p1[:2])
    p2 = a.alloc(2)
    assert set(p2).isdisjoint({p1[2]})
    assert a.high_water == 3  # high-water survives frees
    u = a.utilization()
    assert u["pages_in_use"] == 3 and u["pages_total"] == 4
    with pytest.raises(ValueError, match="double free"):
        a.free([p2[0], p2[0]])
    with pytest.raises(ValueError, match="out of range"):
        a.free([99])


def test_page_allocator_typed_corruption_errors():
    """Double frees and foreign-page frees raise PageAllocatorError — a
    ValueError subclass (so historical handlers keep working) the serving
    resilience layer can route into integrity recovery."""
    assert issubclass(PageAllocatorError, ValueError)
    a = PageAllocator(2)
    p = a.alloc(1)
    a.free(p)
    with pytest.raises(PageAllocatorError, match="double free"):
        a.free(p)
    with pytest.raises(PageAllocatorError, match="foreign"):
        a.free([7])
    assert a.free_set == frozenset({0, 1}), "failed frees must not corrupt"


def test_prefix_index_snapshot_roundtrip():
    """export_state/from_state preserve chain keys, refcounts, page tokens,
    parent links, and LRU order — and the blob is JSON-safe (it rides in
    the serving snapshot's manifest sidecar)."""
    import json

    idx = PrefixIndex(4)
    tokens = list(range(1, 13))  # 3 full pages
    idx.register(tokens, [10, 11, 12])
    fork = tokens[:8] + [77, 78, 79, 80]
    idx.register(fork, [10, 11, 20])
    idx.release(12)  # refcount 0: evictable, but stays resident

    blob = json.loads(json.dumps(idx.export_state()))
    back = PrefixIndex.from_state(blob)
    assert back.pages == idx.pages
    assert all(back.refcount(p) == idx.refcount(p) for p in idx.pages)
    hit = back.lookup(tokens)
    assert hit.pages == [10, 11, 12] and hit.tokens == 12
    hit = back.lookup(fork)
    assert hit.pages == [10, 11, 20]
    # children were rebuilt from parent links: leaf-first eviction still
    # only reaches the refcount-0 leaf, never a shared interior page
    assert back.evict(3) == [12]
    assert back.stats()["hit_tokens"] == idx.stats()["hit_tokens"] + 24


def test_page_allocator_defrag_prefers_low_ids():
    a = PageAllocator(6)
    pages = a.alloc(6)
    a.free(pages)
    a.defrag_order()
    assert a.alloc(2) == [0, 1]


def test_pages_for():
    assert pages_for(0, 4) == 1  # admitted slots always own a page
    assert pages_for(1, 4) == 1
    assert pages_for(4, 4) == 1
    assert pages_for(5, 4) == 2


# ---------------------------------------------------------------------------
# paged == dense numerics (model level, non-contiguous block tables)
# ---------------------------------------------------------------------------


def test_paged_matches_dense_across_page_sizes():
    """Page-size sweep: paged chunked prefill + paged decode logits equal the
    dense one-shot prefill + dense decode — with the slot's pages assigned in
    *reversed* order so the block-table indirection is actually exercised."""
    cfg, bundle, params = _setup()
    prompt = [5, 17, 3, 42, 9, 11, 63, 2, 8, 44, 71, 30]
    n_decode = 3

    cache0 = bundle.init_serve_state(1, 32)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    pos = jnp.arange(len(prompt), dtype=jnp.int32)[None, :]
    ref_logits, ref_cache = jax.jit(bundle.prefill)(params, toks, pos, cache0)
    ref_logits.block_until_ready()
    ref_logits = np.asarray(ref_logits[0])

    for ps in (1, 2, 4, 8):
        W = -(-24 // ps)
        n_pages = 2 * W
        alloc = PageAllocator(n_pages)
        bt = np.full((2, W), n_pages, np.int32)
        pages = alloc.alloc(pages_for(len(prompt) + n_decode, ps))[::-1]
        bt[0, : len(pages)] = pages
        state = bundle.init_paged_state(n_pages, ps, 2, W)
        state = dict(state, block_tables=jnp.asarray(bt))
        step = jax.jit(bundle.prefill_chunk_paged)
        filled, chunk, logits = 0, 5, None
        while filled < len(prompt):
            a = min(chunk, len(prompt) - filled)
            t = np.zeros((2, chunk), np.int32)
            t[0, :a] = prompt[filled:filled + a]
            nv = np.zeros((2,), np.int32)
            nv[0] = a
            logits, state = step(params, jnp.asarray(t), state, jnp.asarray(nv))
            logits.block_until_ready()
            filled += a
        np.testing.assert_allclose(
            np.asarray(logits[0]), ref_logits, atol=1e-5, rtol=1e-5,
            err_msg=f"ps={ps} prefill",
        )

        dstate = ref_cache
        dstep = jax.jit(lambda p, t, s: bundle.decode_step(p, t, s))
        pstep = jax.jit(lambda p, t, s: bundle.decode_step_paged(p, t, s))
        tok = int(np.argmax(ref_logits))
        for i in range(n_decode):
            ld, dstate = dstep(params, jnp.asarray([tok], jnp.int32), dstate)
            ld.block_until_ready()
            lp, state = pstep(params, jnp.asarray([tok, 0], jnp.int32), state)
            lp.block_until_ready()
            np.testing.assert_allclose(
                np.asarray(lp[0]), np.asarray(ld[0]), atol=1e-5, rtol=1e-5,
                err_msg=f"ps={ps} decode step {i}",
            )
            tok = int(np.argmax(np.asarray(ld[0])))


def test_view_indices_lengths_clamp_masks_stale_pages():
    """Regression for the dense-gather over-read: the view must clamp to the
    pages the row's *length* actually uses.  A stale block-table mapping
    beyond the used length (a freed page still holding live-looking
    positions) gathers as fill — K/V = 0, positions = PAD_POS — never as
    data; the partial last page stays fully visible (its unwritten slots are
    masked element-wise by the position pool, not by the clamp)."""
    from repro.serving.kv_cache import (
        PAD_POS,
        gather_pages,
        gather_positions,
        view_indices,
    )

    ps, n_pages = 4, 8
    rng = np.random.default_rng(7)
    k_pool = jnp.asarray(rng.standard_normal((n_pages, ps, 1, 2)), jnp.float32)
    pos_pool = np.full((n_pages, ps), PAD_POS, np.int32)
    # Reversed page order: slot order [7, 6], then stale mappings [5, 3].
    bt = jnp.asarray(np.array([[7, 6, 5, 3]], np.int32))
    length = 6  # pages 7 (full) + 6 (2 of 4 slots written)
    pos_pool[7] = [0, 1, 2, 3]
    pos_pool[6, :2] = [4, 5]
    pos_pool[5] = [0, 1, 2, 3]  # stale: looks causally visible
    pos_pool[3] = [0, 1, 2, 3]
    pos_pool = jnp.asarray(pos_pool)
    lengths = jnp.asarray([length], jnp.int32)

    flat = view_indices(bt, ps, lengths=lengths)
    pos = np.asarray(gather_positions(pos_pool, flat))[0]
    kv = np.asarray(gather_pages(k_pool[None], flat, 0))[0]
    # Used pages, in table order (reversed page ids), fully visible...
    np.testing.assert_array_equal(pos[:ps], [0, 1, 2, 3])
    np.testing.assert_array_equal(pos[ps:ps + 2], [4, 5])
    # ...including the partial page's unwritten tail (element-masked):
    np.testing.assert_array_equal(pos[ps + 2:2 * ps], [PAD_POS, PAD_POS])
    np.testing.assert_array_equal(
        kv[:2 * ps], np.asarray(k_pool)[[7, 6]].reshape(2 * ps, 1, 2)
    )
    # Stale mapped pages beyond ceil(6/4)=2 slots: fill, not data.
    np.testing.assert_array_equal(pos[2 * ps:], PAD_POS)
    np.testing.assert_array_equal(kv[2 * ps:], 0.0)
    # Without the clamp the stale positions leak — the bug being pinned.
    pos_unclamped = np.asarray(gather_positions(pos_pool, view_indices(bt, ps)))
    assert (pos_unclamped[0, 2 * ps:] < PAD_POS).all()


def test_paged_unmapped_pages_are_invisible():
    """Writes through unmapped block-table entries drop; gathers of unmapped
    entries mask out — a row with no pages behaves as an empty cache."""
    cfg, bundle, params = _setup()
    ps, W, n_pages = 4, 4, 8
    state = bundle.init_paged_state(n_pages, ps, 2, W)  # all tables unmapped
    before = jax.tree.map(np.asarray, state)
    step = jax.jit(bundle.prefill_chunk_paged)
    t = np.zeros((2, 4), np.int32)
    t[0] = [5, 17, 3, 42]
    _, state = step(params, jnp.asarray(t), state, jnp.asarray([4, 0], np.int32))
    after = jax.tree.map(np.asarray, state)
    for k in ("k", "v", "pos"):
        np.testing.assert_array_equal(after[k], before[k], err_msg=k)


# ---------------------------------------------------------------------------
# engine boundaries
# ---------------------------------------------------------------------------


def test_engine_paged_long_prompt_beyond_dense_slab():
    """The acceptance path: a prompt longer than the dense slab is rejected
    by the dense engine and served through the paged SP path — with every
    emitted token matching the one-shot dense forward (teacher-forced) and
    physical memory below the dense worst case."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, 40)

    dense = ServingEngine(bundle, params, max_batch=2, max_len=32)
    with pytest.raises(ValueError, match="cannot fit"):
        dense.submit(prompt)

    # logical capacity 64 tokens/slot, physical pool 64 tokens total —
    # half the 2 * 64 dense slab this logical capacity would have pinned
    eng = ServingEngine(
        bundle, params, max_batch=2, max_len=64, prefill_chunk=8,
        page_size=8, max_pages=8,
    )
    req = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert len(req.output) == 6
    assert eng.stats()["pages"]["high_water"] <= 8

    # teacher-forced against the one-shot dense prefill (lm_apply = the
    # fused full-sequence forward, no serving cache at all)
    from repro.models import transformer as T

    toks = list(prompt) + list(req.output)
    x, _ = T.lm_apply(
        params, jnp.asarray([toks], jnp.int32),
        jnp.arange(len(toks), dtype=jnp.int32)[None, :], cfg=cfg, pctx=PCTX,
    )
    w = params["embed"]["table"].T if cfg.tie_embeddings else params["lm_head"]["w"]
    logits = np.asarray(
        jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32), w.astype(jnp.float32))[0]
    )
    for t, tok in enumerate(req.output):
        row = logits[len(prompt) - 1 + t]
        assert row[tok] >= row.max() - GREEDY_TOL, (
            f"step {t}: {tok} vs argmax {int(np.argmax(row))}"
        )


@pytest.mark.parametrize("donate", [False, True], ids=["copied", "donated"])
def test_engine_paged_preemption_requeue_round_trip(donate):
    """Forced preemption: the newest request is evicted when decode growth
    drains the pool, re-queues, re-prefills from prompt + generated tokens,
    and finishes with an oracle-exact chain; pages fully return to the pool.
    With the state donated the steps update the pool in place: a state
    passed to a step is gone after it, and the chains are the same."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(0)
    # 8-page x 4-token pool; each request grows to ceil(20/4) = 5 pages
    eng = donated(ServingEngine(
        bundle, params, max_batch=2, max_len=64, prefill_chunk=4,
        page_size=4, max_pages=8,
    ), donate)
    r1 = eng.submit(rng.integers(1, 90, 9), max_new_tokens=12)
    r2 = eng.submit(rng.integers(1, 90, 9), max_new_tokens=12)
    done = eng.run()
    before = eng.state
    _, eng.state = eng._step(params, jnp.zeros((2,), jnp.int32), before,
                             jnp.zeros((2,), bool))  # no row decodes
    assert before["k"].is_deleted() == donate
    s = eng.stats()
    assert len(done) == 2
    assert s["preemptions"] >= 1, "pool was sized to force a preemption"
    assert len(r1.output) == 12 and len(r2.output) == 12
    assert s["pages"]["pages_in_use"] == 0, "retired pages must return"
    step = _legacy_step(bundle)
    assert_greedy_chain_matches(bundle, params, r1, 2, 64, step)
    assert_greedy_chain_matches(bundle, params, r2, 2, 64, step)


def test_engine_paged_admission_waits_for_pages():
    """Page-exhaustion admission refusal: a request whose prompt pages are
    not free stays queued (strict FCFS) until a retirement frees them."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(1)
    eng = ServingEngine(
        bundle, params, max_batch=2, max_len=20, prefill_chunk=8,
        page_size=4, max_pages=4,
    )
    ra = eng.submit(rng.integers(1, 90, 13), max_new_tokens=3)  # 3 pages
    rb = eng.submit(rng.integers(1, 90, 13), max_new_tokens=3)  # must wait
    eng._admit()
    assert eng.slots[0] is ra
    assert eng.slots[1] is None and eng.queue == [rb], (
        "1 free page < 3 needed: B must stay queued, not grab the free slot"
    )
    done = eng.run()
    assert len(done) == 2 and ra.t_done <= rb.t_first
    assert len(ra.output) == 3 and len(rb.output) == 3
    step = _legacy_step(bundle)
    assert_greedy_chain_matches(bundle, params, ra, 2, 64, step)
    assert_greedy_chain_matches(bundle, params, rb, 2, 64, step)


def test_engine_capacity_retirement_at_last_writable_position():
    """A request that hits capacity retires having written the *last*
    writable cache slot — max_len - p + 1 emitted tokens, all oracle-exact
    (so the token written at the final slot really entered the attention)."""
    cfg, bundle, params = _setup()
    prompt = [5, 17, 3, 42]
    step = _legacy_step(bundle)
    for kw in ({}, {"page_size": 4}):
        eng = ServingEngine(
            bundle, params, max_batch=2, max_len=16, prefill_chunk=4, **kw
        )
        req = eng.submit(prompt, max_new_tokens=100)
        eng.run()
        assert len(req.output) == 16 - len(prompt) + 1, kw
        assert_greedy_chain_matches(bundle, params, req, 2, 64, step)
        if not kw:
            # dense: the retired row's final slot really was written (the
            # pre-PR4 engine stopped one position short)
            assert int(np.asarray(eng.state["pos"])[0, 15]) == 15
            assert int(np.asarray(eng.state["len"])[0]) == 16


def test_engine_paged_single_request_larger_than_pool():
    cfg, bundle, params = _setup()
    eng = ServingEngine(
        bundle, params, max_batch=2, max_len=40, page_size=4, max_pages=4,
    )
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(list(range(1, 31)))  # needs 8 pages, pool holds 4
    # fits at submit, but grows past the pool while running alone
    req = eng.submit(list(range(1, 10)), max_new_tokens=30)
    with pytest.raises(RuntimeError, match="alone needs more pages"):
        eng.run()
    assert req.t_done is None


def test_engine_paged_preempt_disabled_raises():
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(0)
    eng = ServingEngine(
        bundle, params, max_batch=2, max_len=64, prefill_chunk=4,
        page_size=4, max_pages=8, preempt=False,
    )
    eng.submit(rng.integers(1, 90, 9), max_new_tokens=12)
    eng.submit(rng.integers(1, 90, 9), max_new_tokens=12)
    with pytest.raises(RuntimeError, match="preemption is disabled"):
        eng.run()


def test_engine_paged_refuses_families_without_paged_steps():
    cfg = ARCHS["whisper-base"].reduced(vocab_size=97)
    bundle = build_model(cfg, PCTX)
    params = bundle.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="paged"):
        ServingEngine(bundle, params, max_batch=2, max_len=32, page_size=4)


def test_engine_paged_rejects_bad_knobs():
    cfg, bundle, params = _setup()
    with pytest.raises(ValueError, match="page_size"):
        ServingEngine(bundle, params, max_batch=1, max_len=32, page_size=0)
    with pytest.raises(ValueError, match="max_pages"):
        ServingEngine(
            bundle, params, max_batch=1, max_len=32, page_size=4, max_pages=0
        )
    eng = ServingEngine(
        bundle, params, max_batch=1, max_len=30, page_size=4, max_pages=16
    )
    assert eng.cap == 32  # max_len rounds up to whole pages
    with pytest.raises(ValueError, match="paged capacity"):
        eng.submit(list(range(1, 33)))


# ---------------------------------------------------------------------------
# content-addressed prefix cache (engine integration; index-level invariants
# are property-tested in test_prefix_cache.py)
# ---------------------------------------------------------------------------


def _prefix_engine(bundle, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages", 32)
    kw.setdefault("prefix_cache", True)
    return ServingEngine(bundle, params, **kw)


def test_prefix_cache_requires_paged():
    cfg, bundle, params = _setup()
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(
            bundle, params, max_batch=2, max_len=32, prefix_cache=True
        )


def test_engine_prefix_warm_hit_skips_prefill_and_matches_cold():
    """A repeated prompt maps the already-resident pages: zero prefill
    tokens on the warm run, and the decoded chain is *bitwise* the cold
    one — the shared K/V rows feeding it are physically the same pages."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(7)
    prompt = list(rng.integers(1, 90, 25))  # 3 full pages of prompt[:-1]

    eng = _prefix_engine(bundle, params)
    cold = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    cold_prefill = eng.counters["prefill_tokens"]
    assert eng.stats()["prefix"]["indexed_pages"] == 3

    warm = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert warm.output == cold.output
    assert eng.counters["prefill_tokens"] == cold_prefill, (
        "a fully resident prompt must not re-prefill"
    )
    s = eng.stats()["prefix"]
    assert s["hit_tokens"] >= 24 and s["cow_copies"] == 0
    step = _legacy_step(bundle)
    assert_greedy_chain_matches(bundle, params, cold, 2, 64, step)
    assert_greedy_chain_matches(bundle, params, warm, 2, 64, step)


@pytest.mark.parametrize("donate", [False, True], ids=["copied", "donated"])
def test_engine_prefix_cow_divergence_never_mutates_shared_page(donate):
    """A prompt diverging *inside* a resident page decodes oracle-exact via
    a private copy (exactly one COW), and the resident page's K/V bytes are
    untouched, whether or not the steps take the state donated."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(7)
    base = list(rng.integers(1, 90, 25))
    fork = base[:20] + [(t + 1) % 90 + 1 for t in base[20:]]  # page-3 split

    eng = donated(_prefix_engine(bundle, params), donate)
    eng.submit(base, max_new_tokens=6)
    eng.run()
    shared = sorted(eng.prefix.pages)
    k_before = np.asarray(eng.state["k"])[:, shared].copy()
    v_before = np.asarray(eng.state["v"])[:, shared].copy()

    forked = eng.submit(fork, max_new_tokens=6)
    eng.run()
    assert eng.stats()["prefix"]["cow_copies"] == 1
    np.testing.assert_array_equal(
        np.asarray(eng.state["k"])[:, shared], k_before,
        err_msg="COW must copy, never write the shared page",
    )
    np.testing.assert_array_equal(np.asarray(eng.state["v"])[:, shared], v_before)
    step = _legacy_step(bundle)
    assert_greedy_chain_matches(bundle, params, forked, 2, 64, step)


def test_engine_preemption_keeps_shared_prefix_pages():
    """Regression: preempting a request that maps shared (refcount > 1)
    prefix pages must drop only its private suffix — the engine once freed
    the whole block-table row to the allocator, double-freeing pages the
    surviving request was still attending (and the index still owned).
    Both chains must end oracle-exact with refcounts conserved."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(7)
    prompt = list(rng.integers(1, 90, 25))
    # 8-page pool: two 25-token prompts + 20 decode tokens each cannot
    # coexist without preemption, but the 3-page shared prefix fits
    eng = _prefix_engine(bundle, params, max_pages=8)
    a = eng.submit(prompt, max_new_tokens=20)
    b = eng.submit(prompt, max_new_tokens=20)
    eng.run()
    s = eng.stats()
    assert s["preemptions"] >= 1, "pool was sized to force a preemption"
    assert len(a.output) == 20 and len(b.output) == 20
    # conservation after the dust settles: nothing holds a mapping, every
    # surviving indexed page is exactly the allocator's outstanding set
    assert eng.prefix.total_refs() == 0
    assert s["pages"]["pages_in_use"] == len(eng.prefix.pages)
    step = _legacy_step(bundle)
    assert_greedy_chain_matches(bundle, params, a, 2, 64, step)
    assert_greedy_chain_matches(bundle, params, b, 2, 64, step)


# ---------------------------------------------------------------------------
# packed prefill rows: a row carries any slot's chunk at any start
# ---------------------------------------------------------------------------


def _paged_state(bundle, *, ps, W, slots, n_pages, pages):
    """Fresh pool with ``pages[s]`` mapped (in that order) for slot ``s``."""
    bt = np.full((slots, W), n_pages, np.int32)
    for s, pg in enumerate(pages):
        bt[s, : len(pg)] = pg
    state = bundle.init_paged_state(n_pages, ps, slots, W)
    return dict(state, block_tables=jnp.asarray(bt))


def _chunk_rows(C, rows):
    """``(tokens, n_valid)`` of a step whose row ``b`` holds ``rows[b]``."""
    t = np.zeros((len(rows), C), np.int32)
    nv = np.zeros((len(rows),), np.int32)
    for b, toks in enumerate(rows):
        t[b, : len(toks)] = toks
        nv[b] = len(toks)
    return jnp.asarray(t), jnp.asarray(nv)


def _prefill_gather_before_write(params, token_ids, cache, n_valid, *, cfg):
    """The one-row-per-slot prefill step as it was before rows were packed:
    gather the resident view from the pre-chunk pool, clamped to the
    pre-chunk length, attend, then write the chunk.  The oracle for the
    step's default arguments."""
    from repro.core.api import sp_prefill
    from repro.models import transformer as T
    from repro.models.attention import _project_qkv
    from repro.models.layers import dense
    from repro.serving.kv_cache import (
        gather_pages,
        gather_positions,
        view_indices,
        write_coords,
    )

    B, C = token_ids.shape
    n_pages, ps = cache["pos"].shape
    bt, length = cache["block_tables"], cache["len"]
    offs = jnp.arange(C, dtype=jnp.int32)[None, :]
    positions = length[:, None] + offs
    page, off = write_coords(bt, positions, offs < n_valid[:, None], n_pages, ps)
    flat = view_indices(bt, ps, lengths=length)
    old_pos = gather_positions(cache["pos"], flat)
    x = params["embed"]["table"][token_ids].astype(jnp.dtype(cfg.dtype))

    def body(x, xs):
        p_l, kc, vc = xs
        h = T.apply_norm(p_l["ln1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        q, k, v = _project_qkv(p_l["attn"], h, positions, cfg, pctx=PCTX)
        out = sp_prefill(
            q, k, v, positions, gather_pages(kc[None], flat, 0),
            gather_pages(vc[None], flat, 0),
            old_pos, positions, pctx=PCTX, table_pages=bt.shape[1],
        )
        x = x + dense(p_l["attn"]["wo"], out.reshape(B, C, -1), jnp.dtype(cfg.dtype))
        h = T.apply_norm(p_l["ln2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        x = x + T.mlp(p_l["mlp"], h, mlp_type=cfg.mlp_type,
                      compute_dtype=jnp.dtype(cfg.dtype))
        kc = kc.at[page, off].set(k.astype(kc.dtype), mode="drop")
        vc = vc.at[page, off].set(v.astype(vc.dtype), mode="drop")
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = T.apply_norm(params["final_norm"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
    last = x[jnp.arange(B), jnp.clip(n_valid - 1, 0, C - 1)]
    logits = jnp.einsum(
        "bd,dv->bv", last.astype(jnp.dtype(cfg.dtype)),
        T._lm_head_w(params, cfg).astype(jnp.dtype(cfg.dtype)),
    )
    return logits, dict(
        cache, k=ks, v=vs,
        pos=cache["pos"].at[page, off].set(positions, mode="drop"),
        len=length + n_valid,
    )


def test_prefill_default_rows_match_gather_before_write_bit_for_bit():
    """With its default arguments (row = slot, start = ``len``) the
    write-then-gather step gives the old gather-then-write result bit for
    bit: logits, K/V, positions and lengths, over steps that start inside a
    partly used page, with one slot idle in between."""
    cfg, bundle, params = _setup()
    ps, W, C = 4, 6, 5
    state = _paged_state(bundle, ps=ps, W=W, slots=2, n_pages=12,
                         pages=[[9, 2, 7, 4, 0, 11], [3, 8, 1, 5]])
    ref = state
    step = jax.jit(bundle.prefill_chunk_paged)
    old = jax.jit(partial(_prefill_gather_before_write, cfg=cfg))
    rng = np.random.default_rng(11)
    for rows in ([5, 3], [5, 0], [2, 5], [4, 4]):
        t, nv = _chunk_rows(C, [rng.integers(1, 90, n) for n in rows])
        got_logits, state = step(params, t, state, nv)
        want_logits, ref = old(params, t, ref, nv)
        for b, n in enumerate(rows):
            if n:
                np.testing.assert_array_equal(
                    np.asarray(got_logits[b]), np.asarray(want_logits[b]))
        for key in ("k", "v", "pos", "len"):
            np.testing.assert_array_equal(
                np.asarray(state[key]), np.asarray(ref[key]), err_msg=key)


def test_packed_rows_of_one_slot_equal_sequential_steps():
    """One step whose two rows carry consecutive chunks of one slot (starts
    ``s`` and ``s + C``, ``s`` inside a page) equals two sequential steps:
    the second row sees the first row's K/V, written in the same step."""
    cfg, bundle, params = _setup()
    ps, W, C = 4, 6, 5
    base = _paged_state(bundle, ps=ps, W=W, slots=2, n_pages=12,
                        pages=[[9, 2, 7, 4, 0, 11], [3, 8]])
    step = jax.jit(bundle.prefill_chunk_paged)
    rng = np.random.default_rng(5)
    head, c1, c2 = (rng.integers(1, 90, n) for n in (3, C, 4))
    t, nv = _chunk_rows(C, [head, []])
    _, base = step(params, t, base, nv)  # slot 0 at len 3: mid-page
    s = 3

    seq = base
    t, nv = _chunk_rows(C, [c1, []])
    _, seq = step(params, t, seq, nv)
    t, nv = _chunk_rows(C, [c2, []])
    seq_logits, seq = step(params, t, seq, nv)

    t, nv = _chunk_rows(C, [c1, c2])
    packed_logits, packed = step(
        params, t, base, nv, jnp.asarray([0, 0], np.int32),
        jnp.asarray([s, s + C], np.int32),
    )
    assert np.asarray(packed["len"]).tolist() == [s + C + 4, 0]
    for key in ("pos", "len"):
        np.testing.assert_array_equal(np.asarray(packed[key]), np.asarray(seq[key]),
                                      err_msg=key)
    bt = np.asarray(base["block_tables"])[0]
    slots = np.arange(s, s + C + 4)
    page, off = bt[slots // ps], slots % ps
    for key in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(packed[key])[:, page, off], np.asarray(seq[key])[:, page, off],
            err_msg=key)
    np.testing.assert_allclose(np.asarray(packed_logits[1]), np.asarray(seq_logits[0]),
                               atol=1e-5, rtol=1e-5)


def test_packed_empty_row_leaves_pool_and_len_untouched():
    """A row without tokens writes nothing and moves no length, whatever
    slot and start it names — here a mapped slot, at a start beyond its
    length."""
    cfg, bundle, params = _setup()
    ps, W, C = 4, 4, 4
    state = _paged_state(bundle, ps=ps, W=W, slots=2, n_pages=8,
                         pages=[[6, 1, 4], [2, 7, 0]])
    step = jax.jit(bundle.prefill_chunk_paged)
    t, nv = _chunk_rows(C, [[5, 17, 3], [42, 9]])
    _, state = step(params, t, state, nv)
    t, nv = _chunk_rows(C, [[], [11, 63, 2, 8]])
    _, want = step(params, t, state, nv)  # row 0 empty, default slot/start
    _, got = step(params, t, state, nv, jnp.asarray([1, 1], np.int32),
                  jnp.asarray([7, 2], np.int32))
    for key in ("k", "v", "pos", "len"):
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]),
                                      err_msg=key)
    t, nv = _chunk_rows(C, [[], []])
    _, idle = step(params, t, state, nv, jnp.asarray([1, 0], np.int32),
                   jnp.asarray([9, 1], np.int32))
    for key in ("k", "v", "pos", "len"):
        np.testing.assert_array_equal(np.asarray(idle[key]), np.asarray(state[key]),
                                      err_msg=key)


def _admitted_prompt_tokens(eng):
    """Record, per admission, the prompt tokens left to prefill (what the
    unpacked engine would count): every token fed after a prefix hit."""
    seen = []
    admit = eng._admit_into

    def spy(i, qi, req):
        ok = admit(i, qi, req)
        if ok:
            seen.append(len(req._tokens) - 1 - req._filled)
        return ok

    eng._admit_into = spy
    return seen


def test_engine_packs_a_lone_prompt_into_one_step():
    """A request alone takes ``ceil((p-1)/(B*C))`` prefill steps: a 23-token
    prompt at batch 4 and chunk 8 prefills in one step of three rows
    (8 + 8 + 6 tokens, starting mid-page), and its chain is exact."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(4)
    eng = ServingEngine(bundle, params, max_batch=4, max_len=64, prefill_chunk=8,
                        page_size=3)
    req = eng.submit(rng.integers(1, 90, 23), max_new_tokens=5)
    eng.run()
    s = eng.stats()
    assert s["prefill_steps"] == 1 and s["prefill_rows"] == 3
    assert s["prefill_tokens"] == 22 and len(req.output) == 5
    assert_greedy_chain_matches(bundle, params, req, 2, 64, _legacy_step(bundle))


def test_engine_overlapping_prompts_each_get_a_row_while_one_decodes():
    """Two prompts prefilling beside a decoding slot: every step gives each
    at least one row (first pass), the older one takes the free rows
    (second pass), and every chain is exact."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(9)
    eng = ServingEngine(bundle, params, max_batch=4, max_len=64, prefill_chunk=4,
                        page_size=4)
    short = eng.submit([3, 9], max_new_tokens=10)
    eng.run(max_steps=1)  # short is decoding from here on
    a = eng.submit(rng.integers(1, 90, 41), max_new_tokens=4)
    b = eng.submit(rng.integers(1, 90, 37), max_new_tokens=4)
    eng.run(max_steps=1)  # both admitted; a: rows 1, 0, 3 and b: row 2
    assert (a.prefilled, b.prefilled) == (12, 4)
    shared = 1
    while eng._prefilling(a) and eng._prefilling(b):
        fa, fb = a.prefilled, b.prefilled
        eng.run(max_steps=1)
        # a, older, takes the free rows first: three chunks while it lasts
        assert a.prefilled - fa == min(12, 40 - fa) and b.prefilled - fb >= 1
        shared += 1
    assert shared >= 3
    eng.run()
    assert all(r.status == "done" for r in (short, a, b))
    s = eng.stats()
    assert s["prefill_tokens"] == 40 + 36 + 1
    assert s["prefill_rows"] > s["prefill_steps"]
    step = _legacy_step(bundle)
    for r in (short, a, b):
        assert_greedy_chain_matches(bundle, params, r, 2, 64, step)


@pytest.mark.parametrize("donate", [False, True], ids=["copied", "donated"])
def test_engine_preemption_re_prefill_exact_under_packing(donate):
    """A preempted request re-prefills its prompt and output in packed rows
    and resumes exactly; the prefill tokens are what every admission had
    left to prefill, no more and no fewer; the same with the state donated."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(0)
    eng = donated(ServingEngine(bundle, params, max_batch=4, max_len=64, prefill_chunk=4,
                                page_size=4, max_pages=12), donate)
    left = _admitted_prompt_tokens(eng)
    reqs = [eng.submit(rng.integers(1, 90, 17), max_new_tokens=14) for _ in range(2)]
    eng.run()
    s = eng.stats()
    assert s["preemptions"] >= 1, "pool was sized to force a preemption"
    assert s["prefill_rows"] > s["prefill_steps"], "packing never engaged"
    assert s["prefill_tokens"] == sum(left)
    step = _legacy_step(bundle)
    for r in reqs:
        assert len(r.output) == 14
        assert_greedy_chain_matches(bundle, params, r, 2, 64, step)


def test_engine_prefix_warm_hit_packs_the_miss_suffix():
    """A warm request whose prompt shares two resident pages prefills only
    its miss suffix, in packed rows from the hit boundary; cold and warm
    chains are exact."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(7)
    base = list(rng.integers(1, 90, 25))
    fork = base[:16] + list(rng.integers(1, 90, 30))

    eng = _prefix_engine(bundle, params, max_batch=4, prefill_chunk=4)
    left = _admitted_prompt_tokens(eng)
    cold = eng.submit(base, max_new_tokens=4)
    eng.run()
    steps = eng.counters["prefill_steps"]
    warm = eng.submit(fork, max_new_tokens=4)
    eng.run()
    assert left == [24, 45 - 16]
    assert eng.counters["prefill_tokens"] == 24 + 29
    assert eng.counters["prefill_steps"] - steps == 2  # ceil(29 / (4 * 4))
    step = _legacy_step(bundle)
    assert_greedy_chain_matches(bundle, params, cold, 2, 64, step)
    assert_greedy_chain_matches(bundle, params, warm, 2, 64, step)


def test_engine_token_budget_caps_packed_rows():
    """Metered packing: no iteration spends more than the token budget,
    prefill rows and decode tokens together."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(2)
    eng = ServingEngine(bundle, params, max_batch=4, max_len=64, prefill_chunk=4,
                        page_size=4, token_budget=10)
    short = eng.submit([3, 9], max_new_tokens=12)
    eng.run(max_steps=1)
    reqs = [short] + [eng.submit(rng.integers(1, 90, n), max_new_tokens=4)
                      for n in (29, 22)]
    spent_max = 0
    for _ in range(200):
        eng._admit()
        if all(s is None for s in eng.slots) and not eng.queue:
            break
        pre0 = eng.counters["prefill_tokens"]
        dec0 = sum(len(r.output) for r in reqs)
        eng._prefill_tick()
        eng._decode_once()
        spent = (eng.counters["prefill_tokens"] - pre0
                 + sum(len(r.output) for r in reqs) - dec0)
        assert spent <= 10, f"iteration spent {spent} tokens, budget is 10"
        spent_max = max(spent_max, spent)
    assert spent_max == 10
    assert eng.counters["prefill_rows"] > eng.counters["prefill_steps"]
    step = _legacy_step(bundle)
    for r in reqs:
        assert r.t_done is not None
        assert_greedy_chain_matches(bundle, params, r, 2, 64, step)


def test_engine_fewer_prefill_rows_than_slots_exact():
    """Two prefill rows under four slots: three prompts arrive together,
    the first two take a row each and the third waits for a free row; no
    step holds more than two rows of tokens, a prompt longer than two
    chunks takes several steps, and every chain matches the default
    engine's tokens and the oracle."""
    cfg, bundle, params = _setup()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 90, n) for n in (5, 19, 7)]

    def serve(**kw):
        eng = ServingEngine(bundle, params, max_batch=4, max_len=64, prefill_chunk=4,
                            page_size=4, **kw)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run(max_steps=1)
        first = [r.prefilled for r in reqs]
        eng.run()
        return eng, reqs, first

    eng, reqs, first = serve(prefill_rows=2)
    assert first == [4, 4, 0]
    s = eng.stats()
    assert s["prefill_rows"] <= 2 * s["prefill_steps"]
    assert s["prefill_steps"] >= -(-sum(len(p) - 1 for p in prompts) // 8)
    _, ref, _ = serve()
    assert [r.output for r in reqs] == [r.output for r in ref]
    step = _legacy_step(bundle)
    for r in reqs:
        assert_greedy_chain_matches(bundle, params, r, 2, 64, step)


def test_prefill_step_has_prefill_rows_rows():
    """The chunked-prefill step runs on ``(prefill_rows, chunk)`` tokens
    and ``(3, prefill_rows)`` row descriptors; an 11-token prompt fills
    three rows of four (4 + 4 + 2)."""
    cfg, bundle, params = _setup()
    eng = ServingEngine(bundle, params, max_batch=4, max_len=64, prefill_chunk=4,
                        page_size=4, prefill_rows=3)
    shapes = []
    real = eng._chunk_step

    def spy(params, tokens, state, rows):
        shapes.append((tokens.shape, rows.shape, np.asarray(rows)[0].tolist()))
        return real(params, tokens, state, rows)

    eng._chunk_step = spy
    eng.submit(list(range(1, 12)), max_new_tokens=2)
    eng.run(max_steps=1)
    assert shapes == [((3, 4), (3, 3), [4, 4, 2])]


@pytest.mark.parametrize("kw", [dict(prefill_rows=0, page_size=4),
                                dict(prefill_rows=5, page_size=4),
                                dict(prefill_rows=2, page_size=None)])
def test_prefill_rows_validated(kw):
    cfg, bundle, params = _setup()
    with pytest.raises(ValueError, match="prefill_rows"):
        ServingEngine(bundle, params, max_batch=4, max_len=64, prefill_chunk=4, **kw)


def test_prefill_rows_survive_a_snapshot_restart(tmp_path):
    """A restart from a snapshot keeps the step's row count and finishes
    with the tokens of an uninterrupted run."""
    cfg, bundle, params = _setup()
    prompts = [list(range(1, 15)), list(range(20, 29))]
    kw = dict(max_batch=4, max_len=64, prefill_chunk=4, page_size=4, prefill_rows=2)

    eng = ServingEngine(bundle, params, snapshot_dir=str(tmp_path), **kw)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run(max_steps=2)
    eng.snapshot()
    eng2 = ServingEngine.from_snapshot(bundle, params, str(tmp_path))
    assert eng2.prefill_rows == 2
    got = {r.uid: list(r.output) for r in eng2.run()}

    ref = ServingEngine(bundle, params, **kw)
    want = [ref.submit(p, max_new_tokens=5) for p in prompts]
    ref.run()
    assert got == {r.uid: list(w.output) for r, w in zip(reqs, want)}
