"""Batched serving engine: continuous batching, chunked prefill, paged KV.

The TokenRing serving story: the KV cache stays sequence-sharded and
resident (never moves), prefill runs the chunk-resident SP schedule, decode
uses the lse-merge psum (both registered and priced in ``core/strategies.py``
— see docs/serving.md).  This engine adds the request-level machinery around
those steps:

  * fixed ``max_batch`` decode slots; requests join as slots free up
    (continuous batching — per-request cache lengths are native to the
    position-based kernel masking);
  * **chunked prefill**: a joining request's prompt is fed through
    ``bundle.prefill_chunk`` in fixed-size chunks (``prefill_chunk`` tokens)
    that write straight into its cache region — ``O(prompt/chunk)`` steps
    instead of ``O(prompt)`` decode steps — while the other slots keep
    decoding every iteration (no prefill stalls);
  * a **token-budget scheduler**: decoding slots each emit one token per
    iteration (decode is indivisible and never stalls), then prefilling
    slots share the remaining ``token_budget - n_decoding`` tokens FCFS by
    admission order — so the per-iteration total is capped at
    ``max(token_budget, n_decoding)``.  ``None`` means unmetered;
  * a **paged KV cache** (``page_size=``, see ``serving/kv_cache.py`` and
    docs/serving.md §6): KV lives in fixed-size pages drawn from a shared
    pool instead of a contiguous ``max_len`` slab per slot.  Admission is
    gated on free *pages*, not free slots alone; decode grows a request one
    page at a time; when the pool runs dry the lowest-priority (newest)
    request is **preempted** — its pages are freed, it re-queues, and it
    re-prefills from its retained prompt + generated tokens.  Physical
    memory is ``max_pages * page_size`` tokens total, so a long request no
    longer pins worst-case memory for every short one, and per-slot logical
    capacity (``ceil(max_len / page_size)`` pages) can exceed any dense slab
    you could afford to allocate;
  * greedy or temperature sampling; EOS / max-token stop conditions (the EOS
    token is **excluded** from ``output`` and from token throughput — it is
    counted separately in ``stats()["eos_stops"]``);
  * simple FCFS queue with throughput/latency accounting for the benchmark
    harness (``benchmarks/bench_serving.py``);
  * a **resilience layer** (``serving/resilience.py``, docs/resilience.md):
    a deterministic :class:`~repro.serving.resilience.FaultPlan` threaded
    through named tick points, per-request **quarantine/retry** with bounded
    exponential backoff (a fault attributable to one request never kills the
    batch — the request re-queues and recompute-resumes exactly like a
    preemption), a **degrade ladder** (prefix splicing off -> all page
    sharing off -> admissions shed) under persistent faults, a periodic
    :class:`~repro.serving.resilience.CacheAuditor` invariant sweep, and
    **serving-state snapshots** (``snapshot_dir=``) from which a killed
    engine restarts token-exact (:meth:`ServingEngine.from_snapshot`).

Model families without a fused ``prefill_chunk`` but with a cache-style
serve state (``decode_rollback_safe``, e.g. encdec) fall back to filling the
cache token-by-token through ``decode_step`` at admission time — exact but
``O(prompt)`` steps, and it stalls the batch.  Recurrent-state families
(ssm / RG-LRU) are refused with ``NotImplementedError``: their decode steps
advance every row, and recurrent state cannot be rolled back per slot.
"""

from __future__ import annotations

import bisect
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import PAD_POS
from repro.runtime.straggler import StragglerDetector
from repro.runtime.tracing import span
from repro.serving.kv_cache import PageAllocator, PrefixIndex, pages_for
from repro.serving.resilience import (
    CacheAuditor,
    DegradeLadder,
    IntegrityError,
    LoadShedError,
    ServingFault,
    export_serving_state,
    import_serving_state,
)

__all__ = ["Request", "ServingEngine"]


@dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (len,) int32
    max_new_tokens: int = 16
    eos_id: int | None = None
    # filled by the engine:
    output: list = field(default_factory=list)
    stopped_eos: bool = False  # retired by sampling eos_id (not in output)
    status: str = "queued"  # queued | running | retrying | done | failed
    retries: int = 0  # quarantine rounds survived so far
    error: str | None = None  # last fault message (retrying/failed)
    t_submit: float = 0.0
    t_admit: float | None = None  # first admission into a slot
    t_first: float | None = None
    t_done: float | None = None

    @property
    def prefilled(self) -> int:
        """Tokens of the request written to the cache by prefill so far."""
        return getattr(self, "_filled", 0)


class ServingEngine:
    """Continuous-batching engine over a :class:`~repro.models.registry.ModelBundle`.

    Knobs:
      * ``max_batch`` / ``max_len`` — decode slots and per-slot cache
        capacity.  Dense mode allocates ``max_batch x max_len`` up front;
        paged mode rounds ``max_len`` up to ``slot_pages = ceil(max_len /
        page_size)`` pages of *logical* capacity per slot, while physical
        memory is the shared pool below.
      * ``prefill_chunk`` — prompt tokens per row of a chunked-prefill
        step (the static chunk width; prompt tails ride along as partial
        chunks, so there is exactly one compilation).  Paged mode fills the
        rows that prefilling requests leave empty with their later chunks,
        so a request alone prefills ``prefill_rows`` chunks a step.
      * ``prefill_rows`` — paged mode: rows of a chunked-prefill step, so
        ``prefill_rows x prefill_chunk`` prompt tokens a step at most.
        Defaults to ``max_batch`` (one row per slot); fewer rows make a
        step that carries a short prompt cheaper, and a long prompt then
        takes more steps.
      * ``token_budget`` — meters *prefill*: an iteration grants prefilling
        slots at most ``token_budget - n_decoding`` tokens (FCFS).  Decode is
        indivisible — every decoding slot emits one token per iteration
        regardless.  ``None`` disables metering.
      * ``page_size`` — enables the paged KV cache (tokens per page).
        ``None`` keeps the dense per-slot slab.
      * ``max_pages`` — pool size in pages (paged mode).  Defaults to
        ``max_batch * slot_pages`` (dense-equivalent worst case); size it
        *below* that to stop pinning worst-case memory.
      * ``preempt`` — paged mode: when a decode step cannot allocate a page,
        evict the newest request (free its pages, re-queue it, re-prefill
        from its retained tokens) instead of raising.
    """

    def __init__(self, bundle, params, *, max_batch: int, max_len: int,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_chunk: int = 32, prefill_rows: int | None = None,
                 token_budget: int | None = None,
                 page_size: int | None = None, max_pages: int | None = None,
                 preempt: bool = True, prefix_cache: bool = False,
                 fault_plan=None, audit_every: int = 0,
                 max_retries: int = 2, retry_backoff: int = 1,
                 snapshot_dir: str | None = None, snapshot_every: int = 0,
                 straggler: StragglerDetector | None = None):
        if max_retries < 0 or retry_backoff < 1:
            raise ValueError(
                f"need max_retries >= 0 and retry_backoff >= 1, got "
                f"{max_retries}/{retry_backoff}"
            )
        if snapshot_every and snapshot_dir is None:
            raise ValueError("snapshot_every needs snapshot_dir=")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if prefill_rows is not None and (page_size is None
                                         or not 1 <= prefill_rows <= max_batch):
            raise ValueError(
                f"prefill_rows needs the paged KV cache and 1..max_batch "
                f"({max_batch}) rows, got {prefill_rows}"
            )
        if token_budget is not None and token_budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        if prefix_cache and page_size is None:
            raise ValueError(
                "prefix_cache needs the paged KV cache (set page_size=): "
                "cross-request page sharing has no dense-slab analog"
            )
        self.bundle = bundle
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.temperature = temperature
        self.prefill_chunk = prefill_chunk
        self.prefill_rows = max_batch if prefill_rows is None else prefill_rows
        self.token_budget = token_budget
        self.key = jax.random.PRNGKey(seed)
        self.preempt = preempt

        self._paged = page_size is not None
        if self._paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if (bundle.prefill_chunk_paged is None
                    or bundle.decode_step_paged is None
                    or bundle.init_paged_state is None):
                raise NotImplementedError(
                    f"family {bundle.cfg.family!r} has no paged serving "
                    "steps; drop page_size= to serve from the dense slab"
                )
            self.page_size = page_size
            self.slot_pages = pages_for(max_len, page_size)
            self.cap = self.slot_pages * page_size  # logical per-slot tokens
            self.max_pages = (
                max_pages if max_pages is not None
                else max_batch * self.slot_pages
            )
            if self.max_pages < 1:
                raise ValueError(f"max_pages must be >= 1, got {self.max_pages}")
            self.NULL = self.max_pages  # unmapped block-table sentinel
            self.alloc = PageAllocator(self.max_pages)
            self.prefix = PrefixIndex(page_size) if prefix_cache else None
            self._bt = np.full((max_batch, self.slot_pages), self.NULL, np.int32)
            self._bt_dirty = False
            self.state = bundle.init_paged_state(
                self.max_pages, page_size, max_batch, self.slot_pages
            )
            self._step = jax.jit(bundle.decode_step_paged)

            def prefill_chunk_paged(params, tokens, state, rows):
                # ``rows (3, R)``: each row's valid tokens, slot and start,
                # uploaded as one array.  Named for the compiled module.
                return bundle.prefill_chunk_paged(params, tokens, state, *rows)

            self._chunk_step = jax.jit(prefill_chunk_paged)
            self._chunked = True
        else:
            self.page_size = None
            self.prefix = None
            self.cap = max_len
            self.state = bundle.init_serve_state(max_batch, max_len)
            self._step = jax.jit(bundle.decode_step)
            self._chunked = bundle.prefill_chunk is not None
            self._chunk_step = (
                jax.jit(bundle.prefill_chunk) if self._chunked else None
            )
            if not self._chunked and not bundle.decode_rollback_safe:
                # Recurrent families (ssm / RG-LRU): decode_step advances
                # every row's hidden state, and there is no cache-style
                # rollback — the fallback prefill would silently corrupt
                # concurrent requests.
                raise NotImplementedError(
                    f"family {bundle.cfg.family!r} has no chunked prefill and its "
                    "recurrent serve state cannot be rolled back per slot; "
                    "batched serving needs masked decode steps for this family"
                )

        # Slot-reset is a jitted, donated single-slot update: admission cost
        # is one fused scatter, not a host-rebuilt, re-uploaded state tree.
        if self._paged:
            # Paged: only the length resets per slot — freed pages already
            # had their position rows restored to PAD_POS on release, and
            # the block-table row is host-side.  With the prefix cache the
            # length starts at the reused-prefix hit instead of 0: the hit
            # pages' position rows are still valid (they never left the
            # index), so prefill resumes straight at the miss suffix.
            self._reset_slot_to = jax.jit(
                lambda state, i, n: dict(state, len=state["len"].at[i].set(n)),
                donate_argnums=0,
            )
            self._reset_slot = lambda state, i: self._reset_slot_to(state, i, 0)
            self._release_pages = jax.jit(
                lambda state, pages: dict(
                    state,
                    pos=state["pos"].at[pages].set(PAD_POS, mode="drop"),
                ),
                donate_argnums=0,
            )

            def _cow_copy(state, src, dst, keep):
                # Duplicate page ``src`` into private page ``dst``, keeping
                # only the first ``keep`` position entries valid: the K/V
                # rows beyond the divergence are masked (PAD_POS) until the
                # sharer's own prefill overwrites them.  The shared source
                # page is read, never written.
                offs = jnp.arange(self.page_size, dtype=jnp.int32)
                row = jnp.where(offs < keep, state["pos"][src], PAD_POS)
                return dict(
                    state,
                    k=state["k"].at[:, dst].set(state["k"][:, src]),
                    v=state["v"].at[:, dst].set(state["v"][:, src]),
                    pos=state["pos"].at[dst].set(row),
                )

            self._cow_copy = jax.jit(_cow_copy, donate_argnums=0)
        else:
            def _dense_reset(state, i):
                def fix(path, leaf):
                    name = str(getattr(path[-1], "key", ""))
                    if name == "len":
                        return leaf.at[i].set(0)
                    if name == "pos":
                        return leaf.at[i].set(PAD_POS)
                    return leaf

                return jax.tree_util.tree_map_with_path(fix, state)

            self._reset_slot = jax.jit(_dense_reset, donate_argnums=0)

        self.slots: list[Request | None] = [None] * max_batch
        self.queue: list[Request] = []
        self.done: list[Request] = []
        self._uid = 0
        self._hold_decode: set[int] = set()  # first decode deferred (budget)
        self.counters = {
            "decode_steps": 0,
            "prefill_steps": 0,
            "prefill_tokens": 0,
            "prefill_rows": 0,
            "preemptions": 0,
            "eos_stops": 0,
            "faults": 0,
            "quarantines": 0,
            "failures": 0,
            "recoveries": 0,
            "integrity_errors": 0,
            "load_shed": 0,
            "snapshots": 0,
        }

        # ---- resilience layer (serving/resilience.py) -------------------
        self.fault_plan = fault_plan
        self.audit_every = audit_every
        self.max_retries = max_retries
        self.retry_backoff = retry_backoff
        self.snapshot_every = snapshot_every
        self.ladder = DegradeLadder()
        self.auditor = CacheAuditor(self)
        self.straggler = straggler if straggler is not None else StragglerDetector()
        self._tick = 0
        # Host seconds per phase span (``engine.tick``, ``engine.admit``, ...)
        # summed since construction; see ``run`` for what each one covers.
        self.phase_s: dict[str, float] = {}
        if snapshot_dir is not None:
            from repro.checkpoint.manager import CheckpointManager

            self._ckpt = CheckpointManager(snapshot_dir, keep=2)
        else:
            self._ckpt = None

    # ------------------------------------------------------------- API

    def submit(self, prompt, max_new_tokens=16, eos_id=None) -> Request:
        """Queue a request.  The prompt must fit one slot's cache capacity
        (``max_len`` dense, ``slot_pages * page_size`` paged); generation
        that would run past capacity is truncated (the request retires at
        the last writable position — no cache write ever lands out of
        range).  While the degrade ladder is shedding (persistent faults),
        raises :class:`~repro.serving.resilience.LoadShedError` instead of
        queueing work the engine cannot currently take."""
        if not self.ladder.allow_admission:
            self.counters["load_shed"] += 1
            raise LoadShedError(
                f"admission shed: degrade ladder at {self.ladder.name!r} "
                f"after {self.counters['faults']} fault(s)"
            )
        prompt = np.asarray(prompt, np.int32)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size >= self.cap:
            kind = (
                f"paged capacity {self.cap} "
                f"({self.slot_pages} pages x {self.page_size})"
                if self._paged else f"max_len={self.max_len}"
            )
            raise ValueError(
                f"prompt of {prompt.size} tokens cannot fit {kind}"
            )
        if self._paged and pages_for(prompt.size - 1, self.page_size) > self.max_pages:
            raise ValueError(
                f"prompt of {prompt.size} tokens needs "
                f"{pages_for(prompt.size - 1, self.page_size)} pages; the "
                f"pool holds {self.max_pages} — it can never be admitted"
            )
        self._uid += 1
        req = Request(
            uid=self._uid,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=eos_id,
        )
        req._tokens = prompt  # grows to prompt+output on preemption resume
        req._pages = []
        req._ready_tick = 0  # earliest tick _admit may take it (backoff)
        req.t_submit = time.perf_counter()
        self.queue.append(req)
        return req

    def run(self, *, max_steps: int = 10_000):
        """Drive until queue + slots drain (or max_steps iterations).

        Each iteration is one engine *tick*: admit, prefill, decode — then
        the resilience bookkeeping.  Faults handled at their site (per-
        request quarantine) or here (engine-level tick retry) advance the
        degrade ladder; fault-free ticks cool it back down.  Any tick that
        saw a fault ends with a cache audit; periodic audits run every
        ``audit_every`` ticks and periodic snapshots every
        ``snapshot_every``.  Audit violations restore the latest snapshot
        (or raise when none exists).

        Each tick is a host span ``engine.tick`` (carrying the tick number)
        with the phases inside it: ``engine.admit`` per admitted request
        (its uid), ``engine.prefill`` (build the chunk batch, dispatch it),
        ``engine.decode`` (grow pages, build, dispatch), ``engine.sync_bt``
        (block-table upload, inside either), ``engine.sample`` (the host sync
        on the sampled tokens and the retire loop) and ``engine.resilience``
        (audit, ladder, snapshot).  Their seconds add up in ``phase_s``; a
        straggler event keeps its own tick's."""
        for _ in range(max_steps):
            if not self._run_tick():
                break
        return self.done

    def _run_tick(self) -> bool:
        """One tick; False when there was nothing to serve."""
        self._tick += 1
        before = dict(self.phase_s)
        with span("engine.tick", self.phase_s, tick=self._tick):
            t0 = time.perf_counter()
            faults_before = self.counters["faults"]
            try:
                self._admit()
                if all(s is None for s in self.slots) and not self.queue:
                    return False
                if self._chunked:
                    self._prefill_tick()
                self._decode_once()
            except ServingFault as e:
                self._recover(e)
            with span("engine.resilience", self.phase_s):
                self._tick_resilience(faults_before)
            dt = time.perf_counter() - t0
            phases = {k: v - before.get(k, 0.0) for k, v in self.phase_s.items()
                      if v != before.get(k)}
            self.straggler.record(self._tick, dt, phases=phases)
        return True

    def _tick_resilience(self, faults_before: int):
        if self.counters["faults"] > faults_before:
            self._post_recovery_audit()
        else:
            self.ladder.record_clean(self._tick)
            if self.audit_every and self._tick % self.audit_every == 0:
                try:
                    self.auditor.check()
                except IntegrityError as e:
                    self._recover(e)
        if (
            self._ckpt is not None
            and self.snapshot_every
            and self._tick % self.snapshot_every == 0
            and (self.queue or any(s is not None for s in self.slots))
        ):
            self.snapshot()

    # ------------------------------------------------- fault handling

    def _fire(self, point, uid=None):
        """Give the fault plan (when configured) its shot at this tick
        point; raises :class:`InjectedFault` when the plan schedules one."""
        if self.fault_plan is not None:
            self.fault_plan.fire(point, uid=uid)

    def _note_fault(self, err):
        self.counters["faults"] += 1
        self.ladder.record_fault(self._tick)

    def _slot_of(self, uid):
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                return i
        return None

    def _requeue(self, req):
        # Priority = uid order = FCFS: a re-queued request goes back ahead
        # of anything submitted after it.
        uids = [r.uid for r in self.queue]
        self.queue.insert(bisect.bisect_left(uids, req.uid), req)

    def _release_slot(self, i):
        """Take slot ``i``'s request out of the batch, freeing its pages
        and retaining prompt + generated tokens for a recompute-style
        resume (the shared tail of eviction and quarantine)."""
        req = self.slots[i]
        if self._paged:
            self._free_slot_pages(i)
        self.slots[i] = None
        self._hold_decode.discard(i)
        if req.output:
            req._tokens = np.concatenate(
                [req.prompt, np.asarray(req.output, np.int32)]
            )
        req._filled = 0
        req._cached = 0
        req._pages = []
        return req

    def _register_retry(self, req, err):
        """Quarantine bookkeeping for a faulted request (in a slot or still
        queued): bounded exponential backoff, then permanent failure."""
        self.counters["quarantines"] += 1
        req.retries += 1
        req.error = str(err)
        if req.retries > self.max_retries:
            req.status = "failed"
            req.t_done = time.perf_counter()
            self.done.append(req)
            self.counters["failures"] += 1
            return
        req.status = "retrying"
        req._ready_tick = self._tick + self.retry_backoff * (
            2 ** (req.retries - 1)
        )
        self._requeue(req)

    def _quarantine_slot(self, i, err):
        """Per-request failure isolation: pull the faulted request out of
        its slot (rest of the batch keeps decoding) and schedule its retry."""
        self._register_retry(self._release_slot(i), err)

    def _recover(self, err):
        """Engine-level recovery for faults that escape to the run loop.

        Attributable faults quarantine their request; integrity errors
        restore the latest snapshot; bare engine-level faults cost only the
        tick (every injection point fires before state mutation, so the
        serving state stays consistent and the tick simply retries)."""
        self._note_fault(err)
        if isinstance(err, IntegrityError):
            self.counters["integrity_errors"] += 1
            self._restore_or_raise(err)
        elif err.uid is not None:
            i = self._slot_of(err.uid)
            if i is not None:
                self._quarantine_slot(i, err)
            else:
                for qi, r in enumerate(self.queue):
                    if r.uid == err.uid:
                        self.queue.pop(qi)
                        self._register_retry(r, err)
                        break
        self.counters["recoveries"] += 1

    def _post_recovery_audit(self):
        """Invariant sweep after any tick that recovered from a fault; a
        violation means recovery itself corrupted state — restore."""
        v = self.auditor.violations()
        if v:
            err = IntegrityError(v)
            self._note_fault(err)
            self.counters["integrity_errors"] += 1
            self._restore_or_raise(err)
            self.counters["recoveries"] += 1

    def _restore_or_raise(self, err):
        if self._ckpt is None or self._ckpt.latest_step() is None:
            raise err
        self.restore_snapshot()
        self.auditor.check()  # the restored state must itself be clean

    # --------------------------------------------------------- internals

    def _admit(self):
        for i, slot in enumerate(self.slots):
            if slot is not None or not self.queue:
                continue
            qi = self._next_ready()
            if qi is None:
                break
            req = self.queue[qi]
            try:
                with span("engine.admit", self.phase_s, uid=req.uid):
                    self._fire("admit", uid=req.uid)
                    admitted = self._admit_into(i, qi, req)
                if not admitted:
                    # Page exhaustion: strict FCFS — later requests wait
                    # behind the head rather than starving it.
                    break
            except ServingFault as e:
                # Attributable admission fault: the request never entered a
                # slot (every fire point precedes its mutation, alloc'd
                # pages are rolled back) — quarantine it and keep admitting.
                self._note_fault(e)
                self.queue.pop(qi)
                self._register_retry(req, e)

    def _next_ready(self):
        """Queue index of the next admittable request: FCFS over requests
        whose retry backoff has elapsed, skipping *fresh* requests while
        the degrade ladder is shedding (retries keep their admission
        rights — they hold generated progress)."""
        for qi, req in enumerate(self.queue):
            if getattr(req, "_ready_tick", 0) > self._tick:
                continue
            if not self.ladder.allow_admission and req.retries == 0:
                continue
            return qi
        return None

    def _admit_into(self, i, qi, req) -> bool:
        """Admit ``req`` (queue position ``qi``) into free slot ``i``;
        False when the page pool cannot cover it (the caller defers)."""
        hit_tokens = 0
        if self._paged:
            need = pages_for(len(req._tokens) - 1, self.page_size)
            hit = None
            n_hit = 0
            if self.prefix is not None and self.ladder.allow_splice:
                # Reusable prefix among resident pages: only rows the
                # prefill would write (tokens[:-1]) can be reused.
                hit = self.prefix.lookup(req._tokens[:-1])
                n_hit = len(hit.pages)
            self._fire("alloc", uid=req.uid)
            fresh = self._alloc_pages(need - n_hit)
            if fresh is None:
                return False
            cow = hit is not None and hit.cow_page is not None and hit.cow_keep > 0
            if cow:
                try:
                    self._fire("cow", uid=req.uid)
                except ServingFault:
                    self.alloc.free(fresh)  # nothing acquired yet — roll back
                    raise
            if hit is not None:
                self.prefix.acquire(hit.pages)
                hit_tokens = hit.tokens
                if cow:
                    # Divergence inside a resident page: duplicate it into
                    # this request's first private page and keep the shared
                    # rows — the resident page stays untouched (COW).
                    self.state = self._cow_copy(
                        self.state, hit.cow_page, fresh[0], hit.cow_keep
                    )
                    self.prefix.cow_copies += 1
            req._pages = list(hit.pages if hit else []) + fresh
            self._bt[i, :] = self.NULL
            self._bt[i, :need] = req._pages
            self._bt_dirty = True
        self.queue.pop(qi)
        self.slots[i] = req
        req.status = "running"
        if req.t_admit is None:
            req.t_admit = time.perf_counter()
        self.state = (
            self._reset_slot_to(self.state, i, hit_tokens)
            if self._paged else self._reset_slot(self.state, i)
        )
        req._filled = hit_tokens  # prompt tokens already in the cache
        req._cached = hit_tokens  # total cache slots written
        if not self._chunked:
            self._prefill_slot_fallback(i, req)
        elif not self._prefilling(req):
            # Prompt fully resident (single-token prompt, or a full
            # prefix-cache hit): straight to decode.
            req._next_token = int(req._tokens[-1])
        return True

    def _alloc_pages(self, n):
        """Allocate ``n`` pool pages, evicting unreferenced prefix-index
        pages to cover a shortfall; ``None`` when the pool cannot supply
        them (the caller defers admission or preempts)."""
        if n <= 0:
            return []
        short = n - self.alloc.free_pages
        if short > 0 and self.prefix is not None:
            self._drop_indexed(self.prefix.evict(short))
        try:
            return self.alloc.alloc(n)
        except MemoryError:
            return None

    def _drop_indexed(self, pages):
        """Return evicted (refcount-0) index pages to the allocator with
        their position rows masked, so a future owner never attends them."""
        if not pages:
            return
        self.alloc.free(pages)
        padded = np.full((self.slot_pages,), self.NULL, np.int32)
        padded[: len(pages)] = pages
        self.state = self._release_pages(self.state, jnp.asarray(padded))

    def _prefilling(self, req) -> bool:
        return req.prefilled < len(req._tokens) - 1

    def _sync_bt(self):
        if self._paged and self._bt_dirty:
            with span("engine.sync_bt", self.phase_s):
                # Placed like the table a step returns, so a step meets one
                # argument state whether or not the table changed since the
                # last step, and compiles once.
                bt = jax.device_put(self._bt, self.state["block_tables"].sharding)
                self.state = dict(self.state, block_tables=bt)
            self._bt_dirty = False

    # ---- paged bookkeeping ----------------------------------------------

    def _free_slot_pages(self, i):
        """Return slot ``i``'s *private* pages to the pool; restore their
        position rows to PAD_POS so a future owner never attends stale
        entries.  Pages owned by the prefix index (refcount > 1 elsewhere,
        or cached for future hits) are only dereferenced — they stay
        resident with their contents intact."""
        pages = [int(p) for p in self._bt[i] if p != self.NULL]
        if self.prefix is not None:
            # release() returns True for index-owned pages: the index keeps
            # them (other requests may be attending them right now).
            pages = [p for p in pages if not self.prefix.release(p)]
        if pages:
            self.alloc.free(pages)
            padded = np.full((self.slot_pages,), self.NULL, np.int32)
            padded[: len(pages)] = pages
            self.state = self._release_pages(self.state, jnp.asarray(padded))
        self._bt[i, :] = self.NULL
        self._bt_dirty = True

    def _evict(self, i):
        """Preempt slot ``i``: free its pages and re-queue the request.

        The request retains its prompt *and* everything it generated — on
        re-admission it re-prefills ``prompt + output`` through the chunked
        path and resumes decoding where it left off (recompute-style
        preemption: pages are the only thing lost).
        """
        req = self._release_slot(i)
        self.counters["preemptions"] += 1
        req.status = "queued"
        self._requeue(req)

    def _pick_victim(self, requester_i):
        """Lowest-priority (newest) occupant, or None if the requester is
        alone — a single request larger than the whole pool cannot be saved
        by preempting itself."""
        occ = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        i, r = max(occ, key=lambda t: t[1].uid)
        if i == requester_i and len(occ) == 1:
            return None
        return i

    def _grow_pages(self, hold):
        """Page-granular decode growth: map a fresh page for every slot
        whose next write crosses a page boundary, preempting (newest first)
        when the pool is dry."""
        cands = sorted(
            (
                (i, r) for i, r in enumerate(self.slots)
                if r is not None and not self._prefilling(r) and i not in hold
            ),
            key=lambda t: t[1].uid,
        )
        for i, req in cands:
            if self.slots[i] is not req:
                continue  # already evicted as someone's victim
            tbl = req._cached // self.page_size
            if self._bt[i, tbl] != self.NULL:
                continue
            try:
                self._fire("alloc", uid=req.uid)
            except ServingFault as e:
                # Growth-allocation fault: quarantine this request (its
                # output survives — recompute-resume) and keep growing the
                # rest of the batch.
                self._note_fault(e)
                self._quarantine_slot(i, e)
                continue
            while True:
                try:
                    page = self.alloc.alloc(1)[0]
                except MemoryError:
                    if self.prefix is not None:
                        dropped = self.prefix.evict(1)
                        if dropped:
                            # Prefer dropping an unreferenced cached prefix
                            # page over preempting a live request.
                            self._drop_indexed(dropped)
                            continue
                    if not self.preempt:
                        raise RuntimeError(
                            f"KV page pool exhausted ({self.max_pages} pages)"
                            " and preemption is disabled"
                        ) from None
                    victim = self._pick_victim(i)
                    if victim is None:
                        raise RuntimeError(
                            "KV page pool exhausted: the remaining request "
                            "alone needs more pages than the pool holds"
                        ) from None
                    try:
                        self._fire("evict", uid=self.slots[victim].uid)
                    except ServingFault as e:
                        # The eviction itself faulted: quarantine the victim
                        # (frees its pages through the recovery path, with
                        # retry bookkeeping) instead of a clean preemption.
                        self._note_fault(e)
                        self._quarantine_slot(victim, e)
                    else:
                        self._evict(victim)
                    if victim == i:
                        break  # evicted ourselves; skip decode this round
                    continue
                self._bt[i, tbl] = page
                req._pages.append(page)
                self._bt_dirty = True
                break

    # ---- chunked prefill ------------------------------------------------

    def _prefill_tick(self):
        """One scheduler iteration's prefill work: split the token budget
        FCFS across prefilling requests and run a single batched chunk step.
        Its span notes the step's valid and padded (``prefill_rows x chunk``)
        tokens, its rows holding tokens and the requests they belong to."""
        with span("engine.prefill", self.phase_s) as sp:
            valid, rows, requests = self._prefill_dispatch()
            if valid:
                sp.note(valid_tokens=valid,
                        padded_tokens=self.prefill_rows * self.prefill_chunk,
                        rows=rows, requests=requests)

    def _prefill_dispatch(self) -> tuple[int, int, int]:
        """Build the chunk batch and dispatch it; returns its valid tokens,
        the rows holding them and the requests those rows serve.

        Rows are not slots.  First pass: each prefilling request, FCFS by
        uid, gets one chunk in its own slot's row (with fewer rows than
        slots: in the next row, while rows last).  Second pass (paged
        mode): the rows still free carry later chunks of the same requests,
        FCFS, each starting where the request's previous row ends.  The
        token budget caps the total either way."""
        self._fire("prefill_tick")
        prefilling = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and self._prefilling(r)
        ]
        # FCFS by admission order, not slot index: a newer request admitted
        # into a lower slot must not preempt an older request's budget.
        prefilling.sort(key=lambda t: t[1].uid)
        if not prefilling:
            return 0, 0, 0
        n_decode = sum(
            1 for r in self.slots if r is not None and not self._prefilling(r)
        )
        B, C = self.prefill_rows, self.prefill_chunk
        if self.token_budget is None:
            budget = B * C
        else:
            # Decode slots reserve their token first; prefill gets the rest.
            # budget can hit 0 only while something is decoding (the budget
            # is >= 1), so prefill never deadlocks: decode completions free
            # budget on a later iteration.
            budget = max(self.token_budget - n_decode, 0)
        tokens = np.zeros((B, C), np.int32)
        n_valid = np.zeros((B,), np.int32)
        row_slot = np.arange(B, dtype=np.int32)
        row_start = np.zeros((B,), np.int32)
        granted = dict.fromkeys((i for i, _ in prefilling), 0)

        def place(row, i, req):
            nonlocal budget
            start = req._filled + granted[i]
            a = min(len(req._tokens) - 1 - start, C, budget)
            if a <= 0:
                return False
            tokens[row, :a] = req._tokens[start:start + a]
            n_valid[row], row_slot[row], row_start[row] = a, i, start
            granted[i] += a
            budget -= a
            return True

        for n, (i, req) in enumerate(prefilling):
            row = i if B == self.max_batch else n
            if row < B:
                place(row, i, req)
        if self._paged:
            free = iter(np.flatnonzero(n_valid == 0))
            row = next(free, None)
            for i, req in prefilling:
                while row is not None and place(row, i, req):
                    row = next(free, None)
        valid = int(n_valid.sum())
        if not valid:
            return 0, 0, 0
        self._sync_bt()
        step_rows = n_valid
        if self._paged:
            for row in np.flatnonzero(n_valid):
                i, lo = row_slot[row], row_start[row]
                hi = lo + n_valid[row] - 1
                assert (self._bt[i, lo // self.page_size:hi // self.page_size + 1]
                        != self.NULL).all(), f"row {row}: unmapped prompt page"
            step_rows = np.stack([n_valid, row_slot, row_start])
        _, self.state = self._chunk_step(
            self.params, jnp.asarray(tokens), self.state, jnp.asarray(step_rows)
        )
        n_rows = int(np.count_nonzero(n_valid))
        self.counters["prefill_steps"] += 1
        self.counters["prefill_tokens"] += valid
        self.counters["prefill_rows"] += n_rows
        for i, req in prefilling:
            req._filled += granted[i]
            req._cached += granted[i]
            if not self._prefilling(req):
                # Last prompt token is fed by the slot's first decode step.
                req._next_token = int(req._tokens[-1])
                if self.prefix is not None and self.ladder.allow_share:
                    # Index this prompt's full pages for future requests.
                    # Already-shared hit pages are skipped (same key).
                    self.prefix.register(
                        req._tokens[:req._filled],
                        [int(p) for p in self._bt[i] if p != self.NULL],
                    )
                if self.token_budget is not None:
                    # Metered: this iteration's tokens were already spent on
                    # the slot's prefill allocation; its first decode waits
                    # for the next iteration so the budget cap holds.
                    self._hold_decode.add(i)
        return valid, n_rows, sum(1 for g in granted.values() if g)

    # ---- token-by-token fallback (families without prefill_chunk) -------

    def _prefill_slot_fallback(self, i, req):
        """Feed the prompt through decode steps for this slot only.

        Other active slots receive a dummy token and have their length
        rolled back afterwards.  Exact but O(prompt) steps, and it stalls
        the batch — the chunked path above replaces it wherever the model
        family provides ``prefill_chunk``.
        """
        others = [
            (j, s) for j, s in enumerate(self.slots) if s is not None and j != i
        ]
        lens_before = np.asarray(self.state["len"])
        for tok in req._tokens[:-1]:
            toks = np.zeros((self.max_batch,), np.int32)
            toks[i] = tok
            _, self.state = self._step(self.params, jnp.asarray(toks), self.state)
            if others:
                new_len = np.asarray(self.state["len"]).copy()
                for j, _ in others:
                    new_len[j] = lens_before[j]
                self.state = dict(self.state, len=jnp.asarray(new_len))
        req._filled = len(req._tokens) - 1  # prefill complete -> decode phase
        req._cached = req._filled
        req._next_token = int(req._tokens[-1])

    # ---- decode ---------------------------------------------------------

    def _sample(self, logits):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(sub, logits / self.temperature).astype(jnp.int32)

    def _decode_once(self):
        with span("engine.decode", self.phase_s):
            logits, active = self._decode_dispatch()
        if active:
            with span("engine.sample", self.phase_s):
                self._sample_and_retire(logits, active)

    def _decode_dispatch(self):
        """Grow pages, build the decode batch and dispatch the step; returns
        its logits and the slots it decoded (none: nothing to decode)."""
        self._fire("decode_once")
        hold, self._hold_decode = self._hold_decode, set()
        if self._paged:
            self._grow_pages(hold)
        toks = np.zeros((self.max_batch,), np.int32)
        active = []
        for i, req in enumerate(self.slots):
            if req is None or self._prefilling(req) or i in hold:
                continue
            toks[i] = req._next_token
            active.append(i)
        if not active:
            return None, active
        self._sync_bt()
        if self._chunked:
            mask = np.zeros((self.max_batch,), bool)
            mask[active] = True
            logits, self.state = self._step(
                self.params, jnp.asarray(toks), self.state, jnp.asarray(mask)
            )
        else:
            logits, self.state = self._step(
                self.params, jnp.asarray(toks), self.state
            )
        self.counters["decode_steps"] += 1
        return logits, active

    def _sample_and_retire(self, logits, active):
        """Sample the next tokens (the host waits for the step here), then
        stamp, append and retire each decoded slot's request."""
        nxt = np.asarray(self._sample(logits))
        now = time.perf_counter()
        for i in active:
            req = self.slots[i]
            try:
                self._fire("sample", uid=req.uid)
            except ServingFault as e:
                # Sampling fault for this request only: its token this step
                # is discarded with the slot (greedy decode recomputes it
                # identically on resume) — the other slots keep their
                # tokens, the batch never notices.
                self._note_fault(e)
                self._quarantine_slot(i, e)
                continue
            req._cached += 1  # the fed token was written at cache slot len-1
            tok = int(nxt[i])
            if req.t_first is None:
                req.t_first = now
            stopped_eos = req.eos_id is not None and tok == req.eos_id
            if stopped_eos:
                # EOS is a stop *signal*, not an emitted token: it is never
                # appended to the output, never fed back, and never counted
                # toward max_new_tokens or token throughput.
                req.stopped_eos = True
                self.counters["eos_stops"] += 1
            else:
                req.output.append(tok)
                req._next_token = tok
            finished = stopped_eos or len(req.output) >= req.max_new_tokens
            if finished or req._cached >= self.cap:
                # Either done, or at capacity: the cache is full through its
                # last writable position and the next decode step would have
                # nowhere to write its token.
                req.status = "done"
                req.t_done = now
                self.done.append(req)
                self.slots[i] = None
                if self._paged:
                    self._free_slot_pages(i)
                    self.alloc.defrag_order()

    # ------------------------------------------------- snapshot / restore

    def snapshot(self) -> int:
        """Checkpoint the complete serving state under ``snapshot_dir``.

        The device pools (paged K/V + positions + block tables + lengths,
        or the dense slab) go through :class:`CheckpointManager` (atomic,
        sharded); all host-side bookkeeping — block tables, allocator free
        list, prefix-index chain keys/refcounts, scheduler queue, and
        per-request progress — rides in the manifest's ``extra`` sidecar
        (docs/resilience.md documents the format).  Returns the step id
        (the engine tick)."""
        if self._ckpt is None:
            raise RuntimeError("snapshot needs snapshot_dir= at construction")
        self._ckpt.save(
            self._tick, self.state,
            extra={"serving": export_serving_state(self)},
        )
        self.counters["snapshots"] += 1
        return self._tick

    def restore_snapshot(self, step: int | None = None) -> int:
        """Rehydrate this engine from snapshot ``step`` (default latest).

        Device arrays are restored onto their current shardings; host
        bookkeeping comes from the sidecar.  In-flight requests resume
        token-exact (deterministic greedy decode over bit-exact restored
        KV).  Request objects are rebuilt — handles returned by pre-kill
        ``submit`` calls do not track the restored engine."""
        if self._ckpt is None:
            raise RuntimeError("snapshot needs snapshot_dir= at construction")
        if step is None:
            step = self._ckpt.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no committed snapshot under {self._ckpt.dir}"
                )
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, self.state)
        self.state = self._ckpt.restore(step, self.state, shardings=shardings)
        import_serving_state(self, self._ckpt.manifest(step)["extra"]["serving"])
        return step

    @classmethod
    def from_snapshot(cls, bundle, params, snapshot_dir, *, step=None,
                      **overrides):
        """Kill-and-restart: rebuild an engine from its serving snapshot.

        Engine construction kwargs come from the snapshot's own config
        record (``overrides`` win, e.g. to hand the restarted engine a
        fresh ``fault_plan``); device + host state then restore from the
        checkpoint, and ``run()`` resumes every in-flight request where
        the killed engine left it."""
        from repro.checkpoint.manager import CheckpointManager

        ckpt = CheckpointManager(snapshot_dir)
        if step is None:
            step = ckpt.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no committed snapshot under {snapshot_dir}"
                )
        cfg = ckpt.manifest(step)["extra"]["serving"]["config"]
        kwargs = dict(
            max_batch=cfg["max_batch"],
            max_len=cfg["max_len"],
            temperature=cfg["temperature"],
            prefill_chunk=cfg["prefill_chunk"],
            prefill_rows=cfg.get("prefill_rows"),
            token_budget=cfg["token_budget"],
            page_size=cfg["page_size"],
            max_pages=cfg["max_pages"],
            preempt=cfg["preempt"],
            prefix_cache=cfg["prefix_cache"],
            audit_every=cfg["audit_every"],
            max_retries=cfg["max_retries"],
            retry_backoff=cfg["retry_backoff"],
            snapshot_every=cfg["snapshot_every"],
            snapshot_dir=snapshot_dir,
        )
        kwargs.update(overrides)
        eng = cls(bundle, params, **kwargs)
        eng.restore_snapshot(step)
        return eng

    # ------------------------------------------------------------ stats

    def stats(self):
        lat = [r.t_done - r.t_submit for r in self.done if r.t_done]
        ttft = [r.t_first - r.t_submit for r in self.done if r.t_first]
        toks = sum(len(r.output) for r in self.done)
        out = {
            "requests": len(self.done),
            "tokens": toks,
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            "mean_ttft_s": float(np.mean(ttft)) if ttft else 0.0,
            **self.counters,
        }
        out["failed_requests"] = sum(
            1 for r in self.done if r.status == "failed"
        )
        out["degrade"] = {
            "level": self.ladder.level,
            "mode": self.ladder.name,
            "escalations": self.ladder.escalations,
        }
        out["phase_s"] = dict(self.phase_s)
        out["step_time"] = {
            "median_s": self.straggler.median,
            "straggler_events": len(self.straggler.events),
            "slow_ticks": [
                {"tick": t, "seconds": dt, "median_s": med, "phase_s": ph}
                for t, dt, med, ph in self.straggler.events
            ],
        }
        if self._paged:
            out["pages"] = self.alloc.utilization()
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
        if "moe" in self.state:
            # The expert layer's device counters (``models/moe.moe_counters``):
            # reading them waits for the last dispatched step.
            out["moe"] = {k: int(v) for k, v in jax.device_get(self.state["moe"]).items()}
        return out
