"""Serving resilience: fault injection, quarantine, degrade ladder, cache
audits, and serving-state snapshots (serving/resilience.py + engine hooks).

The chaos contract under test: a fault injected at any named tick point is
survived — surviving/retried requests' outputs are **bit-identical** to the
fault-free run (greedy decode is deterministic and quarantine resumes
recompute-style, the same machinery as preemption, whose bitwise-exactness
test_paged_cache.py already pins), the :class:`CacheAuditor` finds zero
invariant violations afterwards, and a killed engine restarted from its
snapshot resumes every in-flight request token-exact.
"""

import json

import numpy as np
import pytest

import jax

from repro.configs import ARCHS
from repro.core.api import ParallelContext
from repro.models import build_model
from repro.serving.engine import ServingEngine
from repro.serving.resilience import (
    TICK_POINTS,
    CacheAuditor,
    DegradeLadder,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    IntegrityError,
    LoadShedError,
)

PCTX = ParallelContext(mesh=None, impl="xla")

_CTX: dict = {}


def _ctx():
    """Module-cached tiny model (params are never mutated by the engine)."""
    if not _CTX:
        cfg = ARCHS["qwen3-1.7b"].reduced(
            n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_head=32,
            d_ff=128, vocab_size=97,
        )
        bundle = build_model(cfg, PCTX)
        _CTX["all"] = (cfg, bundle, bundle.init(jax.random.PRNGKey(0)))
    return _CTX["all"]


# ---------------------------------------------------------------------------
# workloads + fault-free oracles (computed once, compared bitwise)
# ---------------------------------------------------------------------------

_RNG = np.random.default_rng(11)
WORKLOADS = {
    # three distinct prompts, continuous batching over 2 slots
    "standard": [list(_RNG.integers(1, 90, n)) for n in (12, 9, 15)],
    # shared 20-token prefix diverging inside page 3 -> admission COW
    "cow": None,  # filled below (needs the base prompt)
    # two long twins on an 8-page pool -> decode growth must evict
    "tight": None,
}
_BASE = list(_RNG.integers(1, 90, 25))
WORKLOADS["cow"] = [_BASE, _BASE[:20] + [(t + 1) % 90 + 1 for t in _BASE[20:]]]
WORKLOADS["tight"] = [_BASE, list(_BASE)]

ENGINE_KW = dict(
    max_batch=2, max_len=64, prefill_chunk=8, page_size=8, max_pages=32,
    prefix_cache=True, max_retries=5, retry_backoff=1,
)
# cow: one slot serializes base -> fork, so the fork's admission sees the
# base's registered pages and diverges inside page 3 (the COW candidate)
_KW_OVERRIDES = {"tight": {"max_pages": 8}, "cow": {"max_batch": 1}}
_N_NEW = {"standard": 5, "cow": 6, "tight": 20}

_ORACLE: dict = {}


def _run_workload(name, plan=None, **engine_overrides):
    cfg, bundle, params = _ctx()
    kw = dict(ENGINE_KW)
    kw.update(_KW_OVERRIDES.get(name, {}))
    kw.update(engine_overrides)
    eng = ServingEngine(bundle, params, fault_plan=plan, **kw)
    reqs = [eng.submit(p, max_new_tokens=_N_NEW[name]) for p in WORKLOADS[name]]
    eng.run()
    return eng, {r.uid: r for r in reqs}


def _oracle(name):
    """Fault-free outputs by uid, computed once per workload."""
    if name not in _ORACLE:
        eng, reqs = _run_workload(name)
        assert all(r.status == "done" for r in reqs.values())
        assert eng.auditor.violations() == []
        _ORACLE[name] = {uid: list(r.output) for uid, r in reqs.items()}
    return _ORACLE[name]


# ---------------------------------------------------------------------------
# FaultPlan / DegradeLadder units
# ---------------------------------------------------------------------------


def test_fault_plan_scheduled_counts_and_uid_filters():
    plan = FaultPlan([
        FaultSpec("sample", nth=2, times=2),
        FaultSpec("alloc", uid=7, nth=0),
    ])
    hits = []
    for _ in range(6):
        try:
            plan.fire("sample")
        except InjectedFault as e:
            hits.append(e.nth)
    assert hits == [2, 3], "nth/times window, per-point 0-based counters"
    plan.fire("alloc", uid=3)  # other request: no fault
    with pytest.raises(InjectedFault) as ei:
        plan.fire("alloc", uid=7)
    assert ei.value.uid == 7
    assert plan.fired == [("sample", 2, None), ("sample", 3, None),
                          ("alloc", 1, 7)]


def test_fault_plan_bernoulli_deterministic_per_seed():
    def fired_mask(seed):
        p = FaultPlan.bernoulli(0.3, seed=seed, points=("decode_once",))
        out = []
        for _ in range(64):
            try:
                p.fire("decode_once")
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    a, b = fired_mask(5), fired_mask(5)
    assert a == b and any(a) and not all(a)
    assert fired_mask(6) != a


def test_fault_plan_validates_inputs():
    with pytest.raises(ValueError, match="unknown tick point"):
        FaultSpec("defrag")
    with pytest.raises(ValueError, match="nth"):
        FaultSpec("sample", nth=-1)
    with pytest.raises(ValueError, match="rate"):
        FaultPlan(rate=1.0)
    assert set(TICK_POINTS) >= {"admit", "alloc", "evict", "cow", "sample",
                                "prefill_tick", "decode_once"}


def test_degrade_ladder_escalates_and_self_heals():
    lad = DegradeLadder(escalate_after=2, window=8, cooldown=4)
    assert lad.name == "normal" and lad.allow_splice and lad.allow_admission
    lad.record_fault(1)
    lad.record_fault(2)
    assert lad.level == 1 and not lad.allow_splice and lad.allow_share
    lad.record_fault(3)
    lad.record_fault(4)
    assert lad.level == 2 and not lad.allow_share and lad.allow_admission
    lad.record_fault(5)
    lad.record_fault(6)
    assert lad.level == 3 and not lad.allow_admission
    for t in range(7, 11):
        lad.record_clean(t)
    assert lad.level == 2, "one rung per full cooldown"
    for t in range(11, 30):
        lad.record_clean(t)
    assert lad.level == 0, "the ladder is self-healing, never latched"
    # distant faults do not accumulate across the window
    lad2 = DegradeLadder(escalate_after=2, window=4, cooldown=100)
    lad2.record_fault(1)
    lad2.record_fault(50)
    assert lad2.level == 0
    # snapshot round-trip
    blob = json.loads(json.dumps(lad.export_state()))
    lad3 = DegradeLadder()
    lad3.load_state(blob)
    assert lad3.level == lad.level and lad3.escalations == lad.escalations


# ---------------------------------------------------------------------------
# chaos: one injected fault per tick point, outputs bitwise vs fault-free
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload,spec", [
    ("standard", FaultSpec("admit", nth=1)),
    ("standard", FaultSpec("alloc", nth=1)),
    ("standard", FaultSpec("prefill_tick", nth=1)),
    ("standard", FaultSpec("decode_once", nth=2)),
    ("standard", FaultSpec("sample", nth=3)),
    ("cow", FaultSpec("cow", nth=0)),
    ("tight", FaultSpec("evict", nth=0)),
], ids=lambda v: v.point if isinstance(v, FaultSpec) else v)
def test_single_fault_survived_bitwise(workload, spec):
    """Acceptance core: under a single injected fault at each named tick
    point, every request still completes, its output is bit-identical to
    the fault-free run, and the cache auditor finds zero violations."""
    want = _oracle(workload)
    plan = FaultPlan([spec])
    eng, reqs = _run_workload(workload, plan)
    assert plan.fired, f"the planned {spec.point} invocation never happened"
    assert all(r.status == "done" for r in reqs.values()), {
        r.uid: (r.status, r.error) for r in reqs.values()
    }
    assert {uid: list(r.output) for uid, r in reqs.items()} == want
    assert eng.auditor.violations() == []
    assert eng.counters["faults"] >= 1
    if spec.point in ("admit", "alloc", "sample", "cow", "evict"):
        assert eng.counters["quarantines"] >= 1, (
            "attributable faults must quarantine, not kill the batch"
        )


def test_repeated_faults_bounded_backoff_then_permanent_failure():
    """A request whose every sampling attempt faults retries with backoff
    ``max_retries`` times, then fails permanently with its error recorded —
    while the rest of the batch completes bit-identical to fault-free."""
    want = _oracle("standard")
    victim_uid = 2
    plan = FaultPlan([FaultSpec("sample", uid=victim_uid, nth=0, times=99)])
    eng, reqs = _run_workload("standard", plan, max_retries=2)
    bad = reqs[victim_uid]
    assert bad.status == "failed"
    assert bad.retries == 3 and "injected fault at sample" in bad.error
    assert bad.t_done is not None and bad in eng.done
    for uid, r in reqs.items():
        if uid != victim_uid:
            assert r.status == "done" and list(r.output) == want[uid]
    assert eng.counters["failures"] == 1
    assert eng.counters["quarantines"] == 3
    assert eng.auditor.violations() == []
    assert eng.stats()["failed_requests"] == 1


def test_transient_faults_retry_to_identical_output():
    """Two consecutive sampling faults (< max_retries) on one request: it
    retries through backoff and completes with the fault-free output."""
    want = _oracle("standard")
    plan = FaultPlan([FaultSpec("sample", uid=1, nth=0, times=2)])
    eng, reqs = _run_workload("standard", plan, max_retries=5)
    assert reqs[1].status == "done" and reqs[1].retries == 2
    assert {uid: list(r.output) for uid, r in reqs.items()} == want
    assert eng.auditor.violations() == []


# ---------------------------------------------------------------------------
# degrade ladder in the engine: escalation, gating, load shedding
# ---------------------------------------------------------------------------


def test_persistent_faults_climb_to_shedding():
    cfg, bundle, params = _ctx()
    plan = FaultPlan([FaultSpec("decode_once", nth=0, times=9)])
    eng = ServingEngine(bundle, params, fault_plan=plan, **ENGINE_KW)
    req = eng.submit(WORKLOADS["standard"][0], max_new_tokens=4)
    eng.run()
    # engine-level faults only cost their tick: the request still finishes
    assert req.status == "done" and list(req.output) == _oracle("standard")[1][:4]
    assert eng.ladder.level == 3 and eng.ladder.name == "shed"
    assert eng.ladder.escalations == 3
    with pytest.raises(LoadShedError, match="shed"):
        eng.submit([1, 2, 3])
    assert eng.counters["load_shed"] == 1
    assert eng.counters["faults"] == 9 and eng.counters["recoveries"] == 9


def test_ladder_gates_prefix_splicing_then_sharing():
    cfg, bundle, params = _ctx()
    prompt = WORKLOADS["cow"][0]  # 25 tokens -> 3 full prefix pages
    eng = ServingEngine(bundle, params, **ENGINE_KW)
    eng.submit(prompt, max_new_tokens=4)
    eng.run()
    assert len(eng.prefix.pages) == 3
    lookups = eng.prefix.lookup_tokens
    cold_prefill = eng.counters["prefill_tokens"]

    # no_splice: admissions stop consulting the index — the repeat prompt
    # re-prefills in full — but completed prefills still register
    eng.ladder.level = 1
    eng.submit(prompt, max_new_tokens=4)
    eng.run()
    assert eng.prefix.lookup_tokens == lookups, "no lookup at no_splice"
    assert eng.counters["prefill_tokens"] == 2 * cold_prefill

    # no_share (dense fallback): nothing new is registered either
    eng.ladder.level = 2
    fresh = [91, 92, 93, 94, 95, 96] * 4
    eng.submit(fresh, max_new_tokens=4)
    eng.run()
    assert len(eng.prefix.pages) == 3, "no register at no_share"
    assert eng.auditor.violations() == []


# ---------------------------------------------------------------------------
# cache auditor: every violation class is caught; recovery uses snapshots
# ---------------------------------------------------------------------------


def _mid_flight_engine(tmp_path=None, **overrides):
    cfg, bundle, params = _ctx()
    kw = dict(ENGINE_KW)
    if tmp_path is not None:
        kw["snapshot_dir"] = str(tmp_path)
    kw.update(overrides)
    eng = ServingEngine(bundle, params, **kw)
    for p in WORKLOADS["standard"]:
        eng.submit(p, max_new_tokens=_N_NEW["standard"])
    eng.run(max_steps=3)  # prompts part-prefilled: genuinely mid-flight
    assert any(s is not None for s in eng.slots)
    return eng


def test_auditor_flags_each_violation_class():
    eng = _mid_flight_engine()
    assert eng.auditor.violations() == []
    occupied = next(i for i, s in enumerate(eng.slots) if s is not None)
    page = int(eng._bt[occupied, 0])

    def codes():
        return [v.split(":")[0] for v in eng.auditor.violations()]

    # a freed page still mapped by a slot
    eng.alloc._free.append(page)
    eng.alloc._free_set.add(page)
    assert "FREE-MAPPED" in codes() and "ACCOUNT" in codes()
    eng.alloc._free.remove(page)
    eng.alloc._free_set.discard(page)

    # an out-of-range block-table entry
    keep = eng._bt[occupied].copy()
    eng._bt[occupied, -1] = eng.max_pages + 3
    assert "BT-RANGE" in codes()
    eng._bt[occupied] = keep

    # a free slot still mapping a page (and aliasing the occupied slot's)
    empty = next(
        (i for i, s in enumerate(eng.slots) if s is None), None
    )
    if empty is not None:
        eng._bt[empty, 0] = page
        got = codes()
        assert "SLOT-EMPTY" in got and "BT-ALIAS" in got
        eng._bt[empty, 0] = eng.NULL

    # host/device progress divergence
    eng.slots[occupied]._cached += 1
    assert "LEN-MISMATCH" in codes()
    eng.slots[occupied]._cached -= 1

    # prefix refcount drift
    eng.prefix._key_of[page] = b"\x00" * 32
    eng.prefix._page_of[b"\x00" * 32] = page
    eng.prefix._refs[page] = 5
    eng.prefix._tokens[b"\x00" * 32] = (0,)
    eng.prefix._parent[b"\x00" * 32] = b""
    assert "REF-MISMATCH" in codes()

    with pytest.raises(IntegrityError, match="violation"):
        eng.auditor.check()


def test_integrity_error_without_snapshot_is_fatal():
    eng = _mid_flight_engine(audit_every=1)
    page = next(int(p) for p in eng._bt.ravel() if p != eng.NULL)
    eng.alloc._free.append(page)
    eng.alloc._free_set.add(page)
    with pytest.raises(IntegrityError, match="FREE-MAPPED"):
        eng.run()


def test_integrity_error_restores_snapshot_and_completes(tmp_path):
    """Corruption found by the periodic audit feeds the recovery path: the
    engine restores its latest snapshot and finishes bit-identical."""
    want = _oracle("standard")
    eng = _mid_flight_engine(tmp_path, audit_every=1)
    eng.snapshot()
    page = next(int(p) for p in eng._bt.ravel() if p != eng.NULL)
    eng.alloc._free.append(page)
    eng.alloc._free_set.add(page)
    done = eng.run()
    assert eng.counters["integrity_errors"] >= 1
    assert eng.counters["snapshots"] == 1
    by_uid = {r.uid: r for r in done}
    assert {uid: list(r.output) for uid, r in by_uid.items()} == want
    assert all(r.status == "done" for r in by_uid.values())
    assert eng.auditor.violations() == []


# ---------------------------------------------------------------------------
# snapshots: kill-and-restart resumes token-exact
# ---------------------------------------------------------------------------


def test_snapshot_kill_restart_token_exact(tmp_path):
    cfg, bundle, params = _ctx()
    want = _oracle("standard")
    eng = _mid_flight_engine(tmp_path)
    step = eng.snapshot()
    assert eng._ckpt.latest_step() == step
    del eng  # the kill: every live object is gone

    eng2 = ServingEngine.from_snapshot(bundle, params, str(tmp_path))
    eng2.auditor.check()  # restored state passes the full invariant sweep
    done = eng2.run()
    assert {r.uid: list(r.output) for r in done} == want
    assert all(r.status == "done" for r in done)
    assert eng2.auditor.violations() == []
    # prefix index survived with its chain keys: a warm repeat still hits
    prefill_after = eng2.counters["prefill_tokens"]
    warm = eng2.submit(WORKLOADS["standard"][0], max_new_tokens=3)
    eng2.run()
    assert warm.output[:3] == want[1][:3]
    assert eng2.counters["prefill_tokens"] <= prefill_after + ENGINE_KW["prefill_chunk"]


def test_periodic_snapshots_during_run(tmp_path):
    cfg, bundle, params = _ctx()
    eng = ServingEngine(
        bundle, params, snapshot_dir=str(tmp_path), snapshot_every=3,
        **ENGINE_KW,
    )
    for p in WORKLOADS["standard"]:
        eng.submit(p, max_new_tokens=4)
    eng.run()
    assert eng.counters["snapshots"] >= 1
    assert eng._ckpt.latest_step() is not None
    # a restart from the last periodic snapshot is viable mid- or post-run
    eng2 = ServingEngine.from_snapshot(bundle, params, str(tmp_path))
    eng2.auditor.check()
    eng2.run()
    assert eng2.auditor.violations() == []


def test_snapshot_knob_validation():
    cfg, bundle, params = _ctx()
    with pytest.raises(ValueError, match="snapshot_dir"):
        ServingEngine(bundle, params, max_batch=2, max_len=32, snapshot_every=5)
    eng = ServingEngine(bundle, params, max_batch=2, max_len=32)
    with pytest.raises(RuntimeError, match="snapshot_dir"):
        eng.snapshot()
    with pytest.raises(RuntimeError, match="snapshot_dir"):
        eng.restore_snapshot()


def test_straggler_monitor_surfaced_in_stats():
    cfg, bundle, params = _ctx()
    eng = ServingEngine(bundle, params, max_batch=2, max_len=32)
    eng.submit([3, 1, 4], max_new_tokens=3)
    eng.run()
    st = eng.stats()
    assert st["step_time"]["median_s"] > 0.0
    assert st["step_time"]["straggler_events"] == len(st["step_time"]["slow_ticks"])


# ---------------------------------------------------------------------------
# Hypothesis chaos property: random seeded plans never corrupt outputs
# ---------------------------------------------------------------------------


def test_chaos_property_random_fault_plans():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    want = _oracle("standard")

    specs = st.builds(
        FaultSpec,
        point=st.sampled_from(TICK_POINTS),
        nth=st.integers(0, 5),
        times=st.integers(1, 2),
        uid=st.one_of(st.none(), st.integers(1, 3)),
    )

    @hyp.settings(
        max_examples=8, deadline=None,
        suppress_health_check=list(hyp.HealthCheck),
    )
    @hyp.given(faults=st.lists(specs, min_size=1, max_size=3),
               seed=st.integers(0, 2**16))
    def prop(faults, seed):
        plan = FaultPlan(faults, rate=0.02, seed=seed)
        eng, reqs = _run_workload("standard", plan, max_retries=6)
        for uid, r in reqs.items():
            # every completed request is bitwise the fault-free one; only
            # retry exhaustion (bounded, typed) may fail a request
            if r.status == "done":
                assert list(r.output) == want[uid]
            else:
                assert r.status == "failed" and r.error is not None
        assert eng.auditor.violations() == [], plan.fired
        assert all(
            s is None for s in eng.slots
        ) and not eng.queue, "the engine must drain"

    prop()
