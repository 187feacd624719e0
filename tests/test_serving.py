"""Serving engine: chunked prefill correctness, continuous batching, stats.

Oracle convention: greedy chains are compared *teacher-forced* — the oracle
replays the engine's own emitted tokens and asserts each one was within a
tolerance band of the step's max logit.  Comparing two independently-sampled
greedy chains token-for-token is flaky for two reasons (the pre-PR2 form of
this file failed ~1/3 runs): (a) CPU fp jitter flips near-tie argmaxes and
one flipped token diverges the whole suffix, hence the tolerance band; and
(b) *overlapping async executions* of the same CPU executable have been
observed to corrupt logits outright (O(0.1) deviations on otherwise
identical inputs), hence the oracle blocks after every step so at most one
execution is ever in flight.  A bookkeeping bug (wrong cache slot, leaked
state between requests) shifts logits by O(1), far outside the band, so the
tests still pin the engine's actual contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.api import ParallelContext
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine

PCTX = ParallelContext(mesh=None, impl="xla")

# Logit band for accepting a greedy token: far above fp reassociation noise
# (~1e-6), far below any real bookkeeping error (O(1) logit shifts).
GREEDY_TOL = 1e-3


def _setup():
    cfg = ARCHS["qwen3-1.7b"].reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_head=32, d_ff=128,
        vocab_size=97,
    )
    bundle = build_model(cfg, PCTX)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def _oracle_logits_stream(bundle, params, tokens, max_batch, max_len, step):
    """Teacher-forced oracle: feed ``tokens`` one at a time through the
    engine's own jitted decode step in slot 0, yielding the logits after
    each token (i.e. the distribution for the *next* position).

    Every step blocks: overlapping async executions of the same CPU
    executable have been observed to corrupt results on this platform
    (O(0.1) logit deviations, not fp jitter), so the oracle keeps at most
    one execution in flight.
    """
    state = bundle.init_serve_state(max_batch, max_len)
    for tok in tokens:
        toks = np.zeros((max_batch,), np.int32)
        toks[0] = int(tok)
        logits, state = step(params, jnp.asarray(toks), state)
        logits.block_until_ready()
        yield np.asarray(logits[0])


def assert_greedy_chain_matches(bundle, params, req, max_batch, max_len, step):
    """Every emitted token was (near-)argmax of the oracle logits computed on
    the engine's own prefix — tolerance-aware, not near-tie sensitive.

    One teacher-forced pass over prompt + outputs (O(n) decode steps, the
    state carries forward; the chain is never replayed per token).
    """
    tokens = list(req.prompt) + list(req.output[:-1])
    stream = _oracle_logits_stream(bundle, params, tokens, max_batch, max_len, step)
    for _ in range(len(req.prompt) - 1):
        next(stream)  # prompt positions emit no tokens
    for t, (tok, logits) in enumerate(zip(req.output, stream)):
        assert logits[tok] >= logits.max() - GREEDY_TOL, (
            f"req {req.uid} step {t}: token {tok} logit {logits[tok]:.6f} "
            f"vs max {logits.max():.6f} (argmax {int(np.argmax(logits))})"
        )


def _legacy_step(bundle):
    """The 3-arg decode step (no active mask), as the oracle drives it."""
    return jax.jit(lambda p, t, s: bundle.decode_step(p, t, s))


def test_engine_matches_manual_greedy():
    cfg, bundle, params = _setup()
    prompt = [5, 17, 3, 42]
    n_new = 6
    eng = ServingEngine(bundle, params, max_batch=2, max_len=64)
    req = eng.submit(prompt, max_new_tokens=n_new)
    eng.run()
    assert len(req.output) == n_new
    assert_greedy_chain_matches(bundle, params, req, 2, 64, _legacy_step(bundle))


def test_engine_continuous_batching_multiple_requests():
    cfg, bundle, params = _setup()
    eng = ServingEngine(bundle, params, max_batch=2, max_len=64)
    reqs = [eng.submit([3 + i, 9, 27], max_new_tokens=4) for i in range(5)]
    done = eng.run()
    assert len(done) == 5
    for r in reqs:
        assert len(r.output) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.output)
    # each request's chain matches its single-request oracle (slot reuse and
    # interleaving must not leak between requests)
    step = _legacy_step(bundle)
    for r in reqs:
        assert_greedy_chain_matches(bundle, params, r, 2, 64, step)
    s = eng.stats()
    assert s["requests"] == 5 and s["tokens"] == 20
    assert s["mean_latency_s"] >= s["mean_ttft_s"] >= 0.0


def test_eos_excluded_from_output_and_counted_separately():
    """EOS semantics (PR4): the EOS token is a stop signal, not an emitted
    token — it never lands in ``req.output``, never counts toward
    ``max_new_tokens`` or ``stats()['tokens']`` throughput, and is tallied
    separately in ``stats()['eos_stops']``."""
    cfg, bundle, params = _setup()
    prompt = [5, 17, 3, 42]
    eng = ServingEngine(bundle, params, max_batch=2, max_len=64)
    ref = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    assert len(ref.output) == 8 and not ref.stopped_eos
    assert eng.stats()["eos_stops"] == 0

    eos = ref.output[3]
    k = ref.output.index(eos)  # first occurrence ends the rerun
    eng2 = ServingEngine(bundle, params, max_batch=2, max_len=64)
    req = eng2.submit(prompt, max_new_tokens=8, eos_id=eos)
    eng2.run()
    assert req.stopped_eos and req.t_done is not None
    assert req.output == ref.output[:k], "EOS itself must not be emitted"
    s = eng2.stats()
    assert s["tokens"] == k, "throughput counts emitted tokens only"
    assert s["eos_stops"] == 1


def test_eos_on_first_token_still_sets_ttft():
    """A request whose very first sample is EOS emits nothing but still has
    a first-token time (the model did produce a distribution)."""
    cfg, bundle, params = _setup()
    prompt = [5, 17, 3, 42]
    eng = ServingEngine(bundle, params, max_batch=2, max_len=64)
    ref = eng.submit(prompt, max_new_tokens=1)
    eng.run()
    eng2 = ServingEngine(bundle, params, max_batch=2, max_len=64)
    req = eng2.submit(prompt, max_new_tokens=8, eos_id=ref.output[0])
    eng2.run()
    assert req.output == [] and req.stopped_eos
    assert req.t_first is not None and req.t_done is not None
    assert eng2.stats()["tokens"] == 0 and eng2.stats()["eos_stops"] == 1


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def _chunk_fill(bundle, params, prompt, chunk, max_batch, max_len, slot=0):
    """Fill slot ``slot`` with the whole prompt via prefill_chunk steps."""
    state = bundle.init_serve_state(max_batch, max_len)
    step = jax.jit(bundle.prefill_chunk)
    filled = 0
    logits = None
    while filled < len(prompt):
        a = min(chunk, len(prompt) - filled)
        toks = np.zeros((max_batch, chunk), np.int32)
        toks[slot, :a] = prompt[filled:filled + a]
        n_valid = np.zeros((max_batch,), np.int32)
        n_valid[slot] = a
        logits, state = step(
            params, jnp.asarray(toks), state, jnp.asarray(n_valid)
        )
        logits.block_until_ready()  # one in-flight execution at a time
        filled += a
    jax.block_until_ready(state)
    return np.asarray(logits[slot]), state


def test_chunked_prefill_matches_one_shot_across_chunk_sizes():
    """Chunk-size sweep: logits and cache contents equal the fused one-shot
    prefill (cross-chunk causality = the Update() merge, so the sweep is a
    direct test of core/merge.py in the serving path)."""
    cfg, bundle, params = _setup()
    prompt = [5, 17, 3, 42, 9, 11, 63, 2, 8, 44, 71, 30]
    max_len = 32

    cache0 = bundle.init_serve_state(1, max_len)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    pos = jnp.arange(len(prompt), dtype=jnp.int32)[None, :]
    ref_logits, ref_cache = jax.jit(bundle.prefill)(params, toks, pos, cache0)
    ref_logits = np.asarray(ref_logits[0])

    for chunk in (1, 2, 3, 4, 8, len(prompt)):
        logits, state = _chunk_fill(bundle, params, prompt, chunk, 1, max_len)
        np.testing.assert_allclose(logits, ref_logits, atol=1e-5, rtol=1e-5,
                                   err_msg=f"chunk={chunk}")
        assert int(state["len"][0]) == len(prompt)
        for k in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(state[k]), np.asarray(ref_cache[k]),
                atol=1e-5, rtol=1e-5, err_msg=f"chunk={chunk} cache {k}",
            )
        np.testing.assert_array_equal(
            np.asarray(state["pos"]), np.asarray(ref_cache["pos"]),
            err_msg=f"chunk={chunk} cache pos",
        )


def test_chunked_prefill_matches_decode_fill():
    """Chunk filling == token-by-token decode filling: the logits for the
    next token after the prompt agree whichever way the cache was built."""
    cfg, bundle, params = _setup()
    prompt = [7, 21, 3, 42, 9, 11, 5]
    max_len = 32

    # decode-fill: feed every prompt token through the decode step
    state = bundle.init_serve_state(1, max_len)
    step = _legacy_step(bundle)
    logits = None
    for tok in prompt:
        logits, state = step(params, jnp.asarray([tok], jnp.int32), state)
    ref = np.asarray(logits[0])

    for chunk in (1, 3, len(prompt)):
        got, _ = _chunk_fill(bundle, params, prompt, chunk, 1, max_len)
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5,
                                   err_msg=f"chunk={chunk}")


def test_chunked_prefill_skips_inactive_rows():
    """n_valid=0 rows are untouched: cache bytes, positions, and lengths."""
    cfg, bundle, params = _setup()
    max_len = 32
    # fill row 1 first, snapshot, then prefill row 0 and compare row 1
    _, state = _chunk_fill(bundle, params, [9, 13, 27], 2, 2, max_len, slot=1)
    before = jax.tree.map(np.asarray, state)
    step = jax.jit(bundle.prefill_chunk)
    toks = np.zeros((2, 4), np.int32)
    toks[0] = [5, 17, 3, 42]
    _, state = step(
        params, jnp.asarray(toks), state, jnp.asarray([4, 0], np.int32)
    )
    after = jax.tree.map(np.asarray, state)
    assert after["len"][0] == 4 and after["len"][1] == before["len"][1]
    np.testing.assert_array_equal(after["pos"][1], before["pos"][1])
    for k in ("k", "v"):
        np.testing.assert_array_equal(after[k][:, 1], before[k][:, 1])


def test_scheduler_decode_progresses_during_long_prefill():
    """Continuous batching with chunked prefill: a decoding slot emits
    tokens *while* a long prompt prefills chunk-by-chunk (no prefill stall),
    and the long request's chain is still exact."""
    cfg, bundle, params = _setup()
    eng = ServingEngine(
        bundle, params, max_batch=2, max_len=64, prefill_chunk=4,
        token_budget=5,
    )
    short = eng.submit([3, 9], max_new_tokens=12)
    eng.run(max_steps=1)  # short request admitted, starts decoding
    long_prompt = list(np.random.default_rng(0).integers(1, 90, 33))
    long = eng.submit(long_prompt, max_new_tokens=4)

    progressed_during_prefill = False
    for _ in range(200):
        eng._admit()
        if all(s is None for s in eng.slots) and not eng.queue:
            break
        pre0 = eng.counters["prefill_tokens"]
        dec0 = len(short.output) + len(long.output)
        eng._prefill_tick()
        eng._decode_once()
        spent = (eng.counters["prefill_tokens"] - pre0) + (
            len(short.output) + len(long.output) - dec0
        )
        assert spent <= 5, f"iteration spent {spent} tokens, budget is 5"
        if eng._prefilling(long) and len(short.output) > 1:
            progressed_during_prefill = True
    assert long.t_done is not None and short.t_done is not None
    assert progressed_during_prefill, (
        "decode slot made no progress while the long prompt prefilled"
    )
    # budget=5, one decode slot active -> 4 prefill tokens/iteration
    assert eng.counters["prefill_steps"] >= len(long_prompt) // 4
    assert len(long.output) == 4
    step = _legacy_step(bundle)
    assert_greedy_chain_matches(bundle, params, long, 2, 64, step)
    assert_greedy_chain_matches(bundle, params, short, 2, 64, step)


def test_chunked_vs_unchunked_engine_same_outputs():
    """Chunk size must not change results: the emitted chains agree across
    chunk sizes up to a legitimate near-tie flip.  At the first index where
    two chains diverge, *both* tokens must sit within the tolerance band of
    the oracle logits on the (shared) prefix — anything beyond a near-tie
    (a scheduling or cache-write bug) fails."""
    cfg, bundle, params = _setup()
    prompt = [5, 17, 3, 42, 9, 11, 63, 2]
    outs = {}
    for chunk in (1, 3, 8):
        eng = ServingEngine(
            bundle, params, max_batch=2, max_len=64, prefill_chunk=chunk
        )
        req = eng.submit(prompt, max_new_tokens=6)
        eng.run()
        outs[chunk] = req.output
    step = _legacy_step(bundle)
    ref = outs[1]
    for chunk in (3, 8):
        other = outs[chunk]
        div = next((t for t in range(6) if ref[t] != other[t]), None)
        if div is None:
            continue  # identical chains
        shared = prompt + ref[:div]
        *_, logits = _oracle_logits_stream(bundle, params, shared, 2, 64, step)
        for tok in (ref[div], other[div]):
            assert logits[tok] >= logits.max() - GREEDY_TOL, (
                f"chunk={chunk} diverges from chunk=1 at step {div} beyond a "
                f"near-tie: {ref[div]} vs {other[div]}, "
                f"logit {logits[tok]:.6f} vs max {logits.max():.6f}"
            )
    # and every chain is independently oracle-consistent
    for chunk, out in outs.items():
        r = Request(uid=chunk, prompt=np.asarray(prompt, np.int32))
        r.output = list(out)
        assert_greedy_chain_matches(bundle, params, r, 2, 64, step)


def test_engine_counters_show_chunked_speedup():
    """O(prompt/chunk) prefill steps, not O(prompt) decode steps."""
    cfg, bundle, params = _setup()
    prompt = list(range(1, 25))  # 24 tokens
    eng = ServingEngine(bundle, params, max_batch=2, max_len=64, prefill_chunk=8)
    eng.submit(prompt, max_new_tokens=2)
    eng.run()
    s = eng.stats()
    assert s["prefill_tokens"] == len(prompt) - 1
    assert s["prefill_steps"] == 3  # ceil(23 / 8)
    assert s["decode_steps"] == 2


def test_fallback_family_without_prefill_chunk_still_serves():
    """A cache-style family without a fused chunk step (encdec) prefills
    token-by-token at admission and must still reach the decode phase and
    finish — including slot reuse across queued requests (the regression
    where the fallback path never cleared the prefilling phase)."""
    cfg = ARCHS["whisper-base"].reduced(vocab_size=97)
    bundle = build_model(cfg, PCTX)
    assert bundle.prefill_chunk is None and bundle.decode_rollback_safe
    params = bundle.init(jax.random.PRNGKey(0))
    eng = ServingEngine(bundle, params, max_batch=2, max_len=32)
    reqs = [eng.submit([3 + i, 9, 27], max_new_tokens=4) for i in range(3)]
    done = eng.run(max_steps=100)
    assert len(done) == 3
    for r in reqs:
        assert len(r.output) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.output)
    assert eng.stats()["prefill_steps"] == 0  # no chunk path for this family


def test_recurrent_families_refused_with_clear_error():
    """ssm/hybrid serve states cannot be rolled back per slot; the engine
    must refuse them loudly instead of corrupting concurrent requests."""
    for arch in ("falcon-mamba-7b", "recurrentgemma-2b"):
        cfg = ARCHS[arch].reduced(vocab_size=97)
        bundle = build_model(cfg, PCTX)
        params = bundle.init(jax.random.PRNGKey(0))
        with pytest.raises(NotImplementedError, match="rolled back"):
            ServingEngine(bundle, params, max_batch=2, max_len=32)


def test_engine_rejects_bad_knobs():
    cfg, bundle, params = _setup()
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(bundle, params, max_batch=1, max_len=32, prefill_chunk=0)
    with pytest.raises(ValueError, match="token_budget"):
        ServingEngine(bundle, params, max_batch=1, max_len=32, token_budget=0)
    eng = ServingEngine(bundle, params, max_batch=1, max_len=8)
    with pytest.raises(ValueError, match="cannot fit"):
        eng.submit(list(range(8)), max_new_tokens=1)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], max_new_tokens=1)


# ---------------------------------------------------------------------------
# spans, phase times and the request counters they rest on

PHASES = ("engine.tick", "engine.admit", "engine.prefill", "engine.sync_bt",
          "engine.decode", "engine.sample", "engine.resilience")


def test_paged_engine_fills_phase_seconds():
    cfg, bundle, params = _setup()
    eng = ServingEngine(bundle, params, max_batch=2, max_len=64, prefill_chunk=8,
                        page_size=8)
    for i in range(3):
        eng.submit(list(range(1 + i, 20 + i)), max_new_tokens=3)
    eng.run()
    ph = eng.stats()["phase_s"]
    assert set(ph) == set(PHASES)
    assert all(ph[k] > 0.0 for k in PHASES)
    inner = ("engine.admit", "engine.prefill", "engine.decode", "engine.sample",
             "engine.resilience")  # engine.sync_bt lies inside prefill or decode
    assert sum(ph[k] for k in inner) <= ph["engine.tick"]


def test_request_admit_time_and_prefilled_count():
    """``t_admit`` is stamped once, at first admission; ``prefilled`` reads
    the engine's own count of prompt tokens in the cache at every step."""
    cfg, bundle, params = _setup()
    eng = ServingEngine(bundle, params, max_batch=1, max_len=64, prefill_chunk=8,
                        page_size=8)
    a = eng.submit(list(range(1, 21)), max_new_tokens=2)
    b = eng.submit(list(range(2, 12)), max_new_tokens=2)
    assert a.t_admit is None and a.prefilled == 0
    seen = []
    while a.status != "done" or b.status != "done":
        eng.run(max_steps=1)
        for r in (a, b):
            assert r.prefilled == getattr(r, "_filled", 0)
        seen.append(a.prefilled)
    assert seen[:3] == [8, 16, 19]  # 19 prompt tokens prefill; the 20th is decoded
    assert a.t_submit <= a.t_admit <= a.t_first
    assert b.t_admit >= a.t_done  # one slot: b waited for a
    assert b.prefilled == len(b.prompt) - 1


def test_slow_tick_keeps_its_phase_times():
    from repro.runtime.straggler import StragglerDetector

    cfg, bundle, params = _setup()
    eng = ServingEngine(bundle, params, max_batch=1, max_len=64, prefill_chunk=8,
                        page_size=8, straggler=StragglerDetector(warmup=3))
    eng.submit(list(range(1, 5)), max_new_tokens=12)
    eng.run(max_steps=6)  # compiled and warm
    dispatch = eng._decode_dispatch

    def slow_dispatch():
        import time

        time.sleep(0.5)
        return dispatch()

    eng._decode_dispatch = slow_dispatch
    eng.run(max_steps=1)
    eng._decode_dispatch = dispatch
    # A loaded machine may flag other ticks too; the slowed one is the last.
    slow = eng.stats()["step_time"]["slow_ticks"][-1]
    assert slow["tick"] == eng._tick and slow["seconds"] >= 0.5
    assert slow["phase_s"]["engine.decode"] >= 0.5
    assert slow["phase_s"]["engine.decode"] < slow["seconds"]


def test_paged_steps_lower_under_stable_names():
    """The two serving programs compile to modules named after their steps,
    which is how a device profile tells prefill from decode."""
    cfg, bundle, params = _setup()
    eng = ServingEngine(bundle, params, max_batch=2, max_len=64, prefill_chunk=8,
                        page_size=8)
    B, C = 2, 8
    rows = jnp.zeros((3, B), jnp.int32)  # each row's valid tokens, slot, start
    pre = eng._chunk_step.lower(params, jnp.zeros((B, C), jnp.int32), eng.state,
                                rows).as_text()
    dec = eng._step.lower(params, jnp.zeros((B,), jnp.int32), eng.state,
                          jnp.zeros((B,), bool)).as_text()
    assert "module @jit_prefill_chunk_paged" in pre
    assert "module @jit_decode_step_paged" in dec
