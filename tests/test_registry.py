"""SP strategy registry + cost-model planner (single-process, no execution).

Execution-level coverage (the toy plugin actually running through
``sp_attention`` on 8 simulated devices, the planner's window routing) lives
in ``tests/test_strategies.py`` -> ``repro.testing.strategy_check``; here we
pin the registry contract and the planner's byte arithmetic against the
paper's closed forms.
"""

import pytest

from repro.core.compat import make_mesh
from repro.core.strategies import (
    KV_RESIDENT_MARGIN,
    CommCost,
    available_strategies,
    get_strategy,
    ineligible_reason,
    register_strategy,
    registered_strategies,
    resolve_strategy,
    strategy_cost,
    unregister_strategy,
)

BUILTINS = (
    "ring", "ring_bidir", "tokenring", "tokenring_faithful", "ulysses",
    "window", "decode", "prefill",  # serving-side entries (PR 2)
)


def test_builtins_registered():
    names = available_strategies()
    for n in BUILTINS:
        assert n in names, names
    for d in registered_strategies():
        assert callable(d.fn) and callable(d.comm_cost)


def test_cost_models_match_paper_closed_forms():
    """Every registered SP row equals the closed-form byte arithmetic kept in
    benchmarks/bench_comm_volume.py (the paper's Table-1 analog)."""
    from benchmarks.bench_comm_volume import SP_ROWS, closed_form_volumes

    for (S, Hq, Hkv, Dh, P) in [
        (24000, 32, 32, 128, 4),  # paper §4.1 MHA setting
        (32768, 64, 8, 128, 16),  # qwen2-72b GQA setting
        (4096, 8, 2, 64, 8),
    ]:
        oracle = closed_form_volumes(S, Hq, Hkv, Dh, P, b=2)
        for label, name, extra in SP_ROWS:
            cost = strategy_cost(
                get_strategy(name), 1, S, Hq, Hkv, Dh, P, bytes_per_elem=2, **extra
            )
            assert (cost.fwd_bytes, cost.bwd_bytes) == tuple(
                float(x) for x in oracle[label]
            ), (label, S, Hq, Hkv, P)

    # bench's volumes() carries the same assertion internally
    from benchmarks.bench_comm_volume import volumes

    volumes(24000, 32, 32, 128, 4)
    volumes(32768, 64, 8, 128, 16)


def test_auto_gqa_picks_ring_bidir_mha_picks_tokenring():
    # GQA: the bidirectional KV ring moves O(Hkv*D) per direction per step,
    # TokenRing moves O(Hq*D) — the KV ring wins for any Hkv < Hq.
    for (Hq, Hkv, P) in [(8, 2, 4), (64, 8, 16), (16, 8, 4), (32, 4, 8)]:
        got = resolve_strategy("auto", S=128 * P, Hq=Hq, Hkv=Hkv, D=64, P=P)
        assert got == "ring_bidir", (Hq, Hkv, P, got)
    # MHA: equal per-step bytes to leading order; the KV-resident schedule
    # (paper's method) wins within the residency margin.  Head counts chosen
    # indivisible by P so Ulysses' head-sharding shortcut is ineligible.
    for (H, P) in [(6, 4), (4, 8), (32, 12)]:
        got = resolve_strategy("auto", S=128 * P, Hq=H, Hkv=H, D=64, P=P)
        assert got == "tokenring", (H, P, got)


def test_auto_is_the_cost_argmin_with_residency_margin():
    """The planner's choice is reproducible from the registered cost models
    alone — no hidden rules."""
    S, D, b = 4096, 128, 2
    for (Hq, Hkv, P) in [(8, 2, 4), (6, 6, 4), (8, 8, 4), (64, 8, 16), (4, 4, 8)]:
        scores = {}
        for d in registered_strategies():
            if not d.auto_eligible:
                continue
            if ineligible_reason(d, Hq=Hq, Hkv=Hkv, P=P) is not None:
                continue
            cost = strategy_cost(
                d, 1, S, Hq, Hkv, D, P, bytes_per_elem=b,
                travel_dtype="bfloat16",  # accumulator at compute precision
            )
            scores[d.name] = cost.max_direction
        best = min(scores.values())
        expected = min(
            (n for n in scores
             if get_strategy(n).kv_resident and scores[n] <= KV_RESIDENT_MARGIN * best),
            key=lambda n: (scores[n], n),
            default=min(scores, key=lambda n: (scores[n], n)),
        )
        got = resolve_strategy("auto", S=S, Hq=Hq, Hkv=Hkv, D=D, P=P, bytes_per_elem=b)
        assert got == expected, (Hq, Hkv, P, scores, got, expected)


def test_auto_respects_ulysses_head_limit():
    # divisible heads at small P: the all-to-all's constant volume wins …
    assert resolve_strategy("auto", S=4096, Hq=8, Hkv=8, D=128, P=4) == "ulysses"
    # … but GQA head counts indivisible by P knock it out (paper Table 1)
    assert resolve_strategy("auto", S=4096, Hq=64, Hkv=8, D=128, P=16) == "ring_bidir"


def test_window_resolution():
    got = resolve_strategy(
        "auto", S=4096, Hq=8, Hkv=8, D=64, P=4, window=512, layout="contig"
    )
    assert got == "window"
    w = get_strategy("window")
    assert ineligible_reason(w, Hq=8, Hkv=8, P=4, layout="zigzag", window=512)
    assert ineligible_reason(w, Hq=8, Hkv=8, P=4, layout="contig") is not None  # no window
    cost = strategy_cost(
        w, 1, 4096, 8, 8, 64, 4, bytes_per_elem=2, window=512
    )
    # halo = ceil((512-1)/1024) = 1 predecessor shard, one direction
    assert cost.fwd_bytes == 1 * 2 * 1024 * 8 * 64 * 2 and cost.bwd_bytes == 0


def test_cross_attention_prices_kv_on_its_own_length():
    """S_kv != S (cross-attention): KV-circulating strategies scale with the
    encoder length, TokenRing with the decoder length — resident KV is the
    natural fit exactly as models/attention.py claims."""
    kw = dict(S=256, Hq=8, Hkv=4, D=64, P=4, bytes_per_elem=2)
    # self-attention shapes: mild GQA -> the KV ring wins
    assert resolve_strategy("auto", **kw) == "ring_bidir"
    # same heads, but KV rows are a 16x longer encoder sequence
    assert resolve_strategy("auto", S_kv=4096, **kw) == "tokenring"
    rb = strategy_cost(get_strategy("ring_bidir"), 1, 256, 8, 4, 64, 4,
                       bytes_per_elem=2, S_kv=4096)
    rb_self = strategy_cost(get_strategy("ring_bidir"), 1, 256, 8, 4, 64, 4,
                            bytes_per_elem=2)
    assert rb.fwd_bytes == rb_self.fwd_bytes * 16
    tr = strategy_cost(get_strategy("tokenring"), 1, 256, 8, 4, 64, 4,
                       bytes_per_elem=2, S_kv=4096)
    tr_self = strategy_cost(get_strategy("tokenring"), 1, 256, 8, 4, 64, 4,
                            bytes_per_elem=2)
    assert tr.fwd_bytes == tr_self.fwd_bytes  # Q-side traffic: S_kv-independent


def test_hybrid_eligibility_uses_inner_degree():
    """Head divisibility for a hybrid plan is judged at the intra-pod ring
    size, not the flattened SP degree."""
    from repro.core.api import AttnShapes, ParallelContext

    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    shapes = AttnShapes(B=1, Sq=256, Hq=4, Hkv=2, D=32, dtype_bytes=4)
    plan = ParallelContext(
        mesh=mesh, sp_axes=("pod", "model"), strategy="ulysses"
    ).plan(shapes)
    assert plan.inner == "ulysses"


def test_serving_strategies_registered_and_priced():
    """The serving schedules are first-class registry entries: priced by the
    same comm_cost machinery, never run through the sp_attention role."""
    for name in ("decode", "prefill"):
        d = get_strategy(name)
        assert d.serving_side and d.kv_resident and not d.auto_eligible
        # ineligible for the ring-attention role, whatever the shape …
        assert "serving-side" in ineligible_reason(d, Hq=8, Hkv=8, P=4)
        # … so "auto" can never resolve to them
        assert resolve_strategy("auto", S=4096, Hq=8, Hkv=8, D=64, P=4) != name

    # decode: B*S*Hq*(D+2) fp32 scalars through a (P-1)/P bidirectional-ring
    # all-reduce — independent of the cache length S_kv
    B, S, Hq, Hkv, D, P = 2, 1, 8, 2, 64, 4
    cost = strategy_cost(get_strategy("decode"), B, S, Hq, Hkv, D, P)
    expect = (P - 1) / P * B * S * Hq * (D + 2) * 4
    assert cost.fwd_bytes == cost.bwd_bytes == expect
    for skv in (1024, 512 * 1024):
        c = strategy_cost(get_strategy("decode"), B, S, Hq, Hkv, D, P, S_kv=skv)
        assert c.fwd_bytes == expect, "decode cost must not scale with cache"

    # prefill: the same psum at chunk width — linear in the query rows, so a
    # whole prompt is priced by one evaluation at S = prompt_len
    c64 = strategy_cost(get_strategy("prefill"), B, 64, Hq, Hkv, D, P)
    c128 = strategy_cost(get_strategy("prefill"), B, 128, Hq, Hkv, D, P)
    assert c128.fwd_bytes == 2 * c64.fwd_bytes
    assert c64.fwd_bytes == (P - 1) / P * B * 64 * Hq * (D + 2) * 4

    # single device: serving needs no wire at all
    assert strategy_cost(get_strategy("decode"), B, S, Hq, Hkv, D, 1).total == 0.0

    # resident-chunk prefill vs circulating the prompt's KV every chunk: for
    # a long prompt the psum schedule wins by orders of magnitude (the
    # arithmetic bench_serving.py tabulates)
    prompt, chunk = 32768, 256
    resident = strategy_cost(get_strategy("prefill"), 1, prompt, Hq, Hkv, D, P)
    ring_per_chunk = strategy_cost(
        get_strategy("ring_bidir"), 1, chunk, Hq, Hkv, D, P, S_kv=prompt
    )
    ring_total = ring_per_chunk.max_direction * (prompt // chunk)
    assert resident.max_direction < ring_total / 10


def test_plan_decode_and_prefill_carry_cost():
    """plan_decode / plan_prefill resolve the serving schedule with priced
    plans — the serving analog of the training plan surface."""
    from repro.core.api import AttnShapes, ParallelContext

    mesh = make_mesh((1, 1), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",))
    shapes = AttnShapes(B=2, Sq=1, Hq=8, Hkv=2, D=64, Sk=4096, dtype_bytes=4)
    plan = pctx.plan_decode(shapes=shapes)
    assert plan.kind == "decode" and plan.strategy == "decode"
    expect = strategy_cost(
        get_strategy("decode"), 2, 1, 8, 2, 64, pctx.sp_degree,
        bytes_per_elem=4, S_kv=4096,
    )
    assert plan.cost == expect

    cshapes = AttnShapes(B=2, Sq=32, Hq=8, Hkv=2, D=64, Sk=4096, dtype_bytes=4)
    pplan = pctx.plan_prefill(shapes=cshapes)
    assert pplan.kind == "prefill" and pplan.strategy == "prefill"
    assert pplan.cost == strategy_cost(
        get_strategy("prefill"), 2, 32, 8, 2, 64, pctx.sp_degree,
        bytes_per_elem=4, S_kv=4096,
    )
    # shapes are optional (sp_decode's hot path passes them; manual callers
    # may not care about the cost annotation)
    assert pctx.plan_decode().cost is None


def test_explicit_serving_strategy_rejected_by_attention_plan():
    """strategy='decode' on the training path is a planning error, not a
    silent mis-schedule."""
    from repro.core.api import AttnShapes, ParallelContext

    mesh = make_mesh((1, 1), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), strategy="decode")
    with pytest.raises(ValueError, match="serving-side"):
        pctx.plan(AttnShapes(B=1, Sq=256, Hq=4, Hkv=4, D=32))


def test_register_duplicate_name_raises():
    fn = lambda *a, **k: None  # noqa: E731
    cc = lambda *a, **k: CommCost(0.0, 0.0)  # noqa: E731
    register_strategy("toy_dup", fn, comm_cost=cc)
    try:
        with pytest.raises(ValueError, match="already registered"):
            register_strategy("toy_dup", fn, comm_cost=cc)
    finally:
        unregister_strategy("toy_dup")


def test_register_unknown_capability_raises():
    fn = lambda *a, **k: None  # noqa: E731
    cc = lambda *a, **k: CommCost(0.0, 0.0)  # noqa: E731
    with pytest.raises(ValueError, match="unknown capability"):
        register_strategy("toy_bad", fn, comm_cost=cc, supports_warp_drive=True)
    assert "toy_bad" not in available_strategies()


def test_unknown_strategy_name_raises():
    with pytest.raises(ValueError, match="unknown SP strategy"):
        get_strategy("nope")
    with pytest.raises(ValueError, match="unknown SP strategy"):
        resolve_strategy("nope", S=1024, Hq=4, Hkv=4, D=64, P=4)


def test_no_eligible_strategy_raises():
    # window set but contiguous-layout requirement violated for every
    # window-capable strategy -> clear planner error, not a silent fallback
    with pytest.raises(ValueError, match="no eligible SP strategy"):
        resolve_strategy(
            "auto", S=1024, Hq=4, Hkv=4, D=64, P=4, window=128, layout="zigzag"
        )


def test_plan_surface_single_process():
    """Planning is pure shape arithmetic: exercisable on one device."""
    from repro.core.api import AttnShapes, ParallelContext

    mesh = make_mesh((1, 1), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",), strategy="auto")
    shapes = AttnShapes(B=2, Sq=256, Hq=6, Hkv=6, D=32, dtype_bytes=4)
    plan = pctx.plan(shapes, causal=True)
    assert plan.kind == "attention" and plan.strategy == "tokenring"
    assert plan.cost is not None and plan.cost.fwd_bytes == plan.cost.bwd_bytes

    # windowed layers route to the halo strategy whatever was configured
    wplan = ParallelContext(
        mesh=mesh, sp_axes=("model",), strategy="tokenring", layout="contig"
    ).plan(shapes, causal=True, window=64)
    assert wplan.strategy == "window"

    with pytest.raises(ValueError, match="unknown SP strategy"):
        ParallelContext(mesh=mesh, sp_axes=("model",), strategy="bogus").plan(shapes)
    with pytest.raises(ValueError, match="not in mesh axes"):
        ParallelContext(mesh=mesh, sp_axes=("ring",)).plan(shapes)


def test_plan_hybrid_inner_validation():
    from repro.core.api import AttnShapes, ParallelContext

    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    shapes = AttnShapes(B=1, Sq=256, Hq=4, Hkv=4, D=32, dtype_bytes=4)
    plan = ParallelContext(
        mesh=mesh, sp_axes=("pod", "model"), strategy="tokenring"
    ).plan(shapes)
    assert plan.inner == "tokenring" and plan.strategy == "tokenring"
    # a non-hybrid-capable schedule raises identically whether it was asked
    # for via inner_strategy= or strategy= — never a silent swap
    with pytest.raises(ValueError, match="multi-pod hybrid"):
        ParallelContext(
            mesh=mesh, sp_axes=("pod", "model"), strategy="tokenring",
            inner_strategy="ring_bidir",  # declared hybrid_inner_ok=False
        ).plan(shapes)
    with pytest.raises(ValueError, match="multi-pod hybrid"):
        ParallelContext(
            mesh=mesh, sp_axes=("pod", "model"), strategy="ring_bidir"
        ).plan(shapes)


def test_hybrid_rejects_unknown_inner_kwargs():
    """A misspelled extra (``travle_dtype``) must raise, naming the accepted
    extras — the pre-PR4 ``hybrid_sp`` silently filtered unknown kwargs, so
    the schedule ran at its default and the typo was never surfaced."""
    import jax.numpy as jnp

    from repro.core.hybrid import hybrid_sp

    x = jnp.zeros((1, 4, 2, 8))
    p = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="travle_dtype"):
        hybrid_sp(
            x, x, x, p, p, pod_axis="pod", axis_name="model",
            inner="tokenring", travle_dtype="bfloat16",
        )
    # the error names the extras the inner strategy does accept
    with pytest.raises(ValueError, match="travel_dtype"):
        hybrid_sp(
            x, x, x, p, p, pod_axis="pod", axis_name="model",
            inner="tokenring", travle_dtype="bfloat16",
        )


def test_paged_block_table_cost_term():
    """``table_pages`` prices the paged cache's per-step block-table
    broadcast on top of the (page-location-independent) psum payload, for
    both serving schedules, and ``plan_decode``/``plan_prefill`` thread it."""
    from repro.core.api import AttnShapes, ParallelContext

    B, S, Hq, Hkv, D, P, W = 2, 1, 8, 2, 64, 4, 128
    extra = (P - 1) / P * B * W * 4  # int32 table rows through the same ring
    for name, S_ in (("decode", 1), ("prefill", 32)):
        base = strategy_cost(get_strategy(name), B, S_, Hq, Hkv, D, P)
        paged = strategy_cost(
            get_strategy(name), B, S_, Hq, Hkv, D, P, table_pages=W
        )
        assert paged.fwd_bytes == base.fwd_bytes + extra, name
        # the page *data* never moves: the term is cache-length independent
        long = strategy_cost(
            get_strategy(name), B, S_, Hq, Hkv, D, P, table_pages=W,
            S_kv=512 * 1024,
        )
        assert long.fwd_bytes == paged.fwd_bytes, name

    mesh = make_mesh((1, 1), ("data", "model"))
    pctx = ParallelContext(mesh=mesh, sp_axes=("model",))
    shapes = AttnShapes(B=2, Sq=1, Hq=8, Hkv=2, D=64, Sk=4096, dtype_bytes=4)
    plan = pctx.plan_decode(shapes=shapes, table_pages=W)
    assert plan.cost == strategy_cost(
        get_strategy("decode"), 2, 1, 8, 2, 64, pctx.sp_degree,
        bytes_per_elem=4, S_kv=4096, table_pages=W,
    )
    pplan = pctx.plan_prefill(shapes=shapes, table_pages=W)
    assert pplan.cost == strategy_cost(
        get_strategy("prefill"), 2, 1, 8, 2, 64, pctx.sp_degree,
        bytes_per_elem=4, S_kv=4096, table_pages=W,
    )


def test_choose_strategy_backcompat():
    from repro.core.api import choose_strategy

    assert choose_strategy("auto", 8, 2, 4) == "ring_bidir"
    assert choose_strategy("auto", 32, 32, 4) == "tokenring"
    for s in BUILTINS:
        assert choose_strategy(s, 8, 8, 4) == s
