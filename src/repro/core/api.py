"""Public sequence-parallel attention API.

Models never call the strategy functions directly: they call
:func:`sp_attention` / :func:`sp_decode` with *global* (logically unsharded)
arrays and a :class:`ParallelContext`.  The API owns the ``shard_map`` region:
activations enter sharded ``P(data, (pod, model), None, None)``, the chosen
strategy runs its explicit ppermute schedule inside, and the result leaves
with the same sharding — the surrounding ``jit`` (projections, FFN, loss)
stays in ordinary XLA-SPMD land.

Strategy selection is registry-driven (see ``core/strategies.py`` and
DESIGN.md): each strategy module registers an ``SPStrategy`` descriptor with
its capabilities and a closed-form ``comm_cost`` model, and
:meth:`ParallelContext.plan` resolves the configured name — or ``"auto"`` by
byte-count argmin over eligible strategies — into an :class:`ExecutionPlan`
holding the uniform shard_map-local callable.  Built-ins:

  * ``"tokenring"``           — paper's method, TPU-adapted (default)
  * ``"tokenring_faithful"``  — paper's Algorithm 1 literal schedule
  * ``"ring"`` / ``"ring_bidir"`` — baselines
  * ``"ulysses"``             — all-to-all head parallelism (head-count bound)
  * ``"window"``              — halo-exchange sliding-window attention
  * ``"auto"``                — per-strategy ``comm_cost`` argmin: TokenRing
    moves O(Hq*D) per direction per step while the bidirectional KV ring moves
    O(Hkv*D); under GQA (Hkv << Hq) the KV ring wins, and under MHA TokenRing
    (resident KV, within the KV-residency margin) wins — unless the head
    counts divide the SP degree at small P, where Ulysses' constant-volume
    all-to-all is genuinely cheapest (DESIGN.md §2 has the full decision
    table).  The decision is static — it depends only on shapes.

With two SP axes (multi-pod) the planner chooses the paper's Case-Study-III
hybrid decomposition: inter-pod KV ring outside, the chosen intra-pod
strategy inside.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compat import shard_map
from repro.core.strategies import (
    KV_RESIDENT_MARGIN,
    CommCost,
    SPStrategy,
    _decision_travel_dtype,
    attention_compute_flops,
    ceil_div,
    get_strategy,
    ineligible_reason,
    resolve_strategy,
    strategy_cost,
)

__all__ = [
    "ParallelContext",
    "ExecutionPlan",
    "AttnShapes",
    "PREFILL_CANDIDATES",
    "sp_attention",
    "sp_decode",
    "sp_decode_paged",
    "sp_prefill",
    "sp_scan",
    "choose_strategy",
]

# The prefill arbitration pool (``ParallelContext.choose_prefill_strategy``):
# the resident-psum chunk path plus the two prefill rings of
# ``core/prefill_rings.py`` — opposite bets on what moves over the wire,
# decided per request by the KV:Q byte ratio after prefix-cache hits are
# subtracted from the query side.
PREFILL_CANDIDATES = ("prefill", "passkv_ring", "passq_ring")


@dataclass(frozen=True)
class AttnShapes:
    """Static attention shapes the planner needs (global, unsharded)."""

    B: int
    Sq: int
    Hq: int
    Hkv: int
    D: int
    Sk: int | None = None  # defaults to Sq (self-attention)
    dtype_bytes: int = 2  # wire size of a q/k/v element

    @property
    def seq_kv(self) -> int:
        return self.Sq if self.Sk is None else self.Sk


@dataclass(frozen=True)
class ExecutionPlan:
    """A validated, resolved shard_map execution: what ``sp_attention`` /
    ``sp_decode`` / ``sp_scan`` actually run.

    ``local_fn`` is the uniform per-shard callable (strategy schedule already
    bound); ``cost`` is the resolved strategy's modeled per-device link bytes
    for one pass of the planned step — attention plans always carry it,
    decode/prefill plans carry it when ``shapes`` were provided, scan plans
    never do.
    """

    kind: str  # "attention" | "decode" | "scan"
    strategy: str | None  # resolved concrete strategy name
    inner: str | None  # intra-pod strategy when the hybrid wraps it
    mesh: Mesh
    in_specs: tuple
    out_specs: Any
    local_fn: Callable[..., Any]
    sp_axes: tuple[str, ...]
    sp_degree: int
    cost: CommCost | None = None
    # Modeled per-device attention dot FLOPs of the planned pass (None for
    # decode/prefill/scan plans) — the compute half of the overlap-aware
    # ``max(compute, link)`` step-time model (docs/overlap.md).
    compute_flops: float | None = None
    # Whether the resolved schedule's transfers overlap compute (the
    # SPStrategy.pipelines capability) — False schedules never get a
    # pipelined time below the sequential one.
    pipelines: bool = True
    # When the plan was arbitrated against a physical Topology
    # (``plan(topology=...)``): the scored candidates and the winner, so
    # launchers can record *why* this schedule runs on this fabric.
    topology_decision: dict | None = None
    # Kernel choice the per-shard callable dispatches to (impl + decode tile
    # + gather-vs-fused path for paged decode) — recorded so dryrun plan
    # records and the static gate see *which* kernel serves the step, not
    # just which schedule.
    kernel: dict | None = None

    def modeled_times(
        self,
        *,
        link_bw: float,
        peak_flops: float,
        bidir_links: bool = True,
    ) -> dict | None:
        """Sequential-vs-pipelined modeled wall time of the planned pass.

        ``sequential_s`` charges compute + link serially (the legacy
        merge→rotate dependency chain); ``pipelined_s`` is the overlap
        executor's ``max(compute, link)``.  ``overlap_fraction`` is the
        modeled saving — 0 when one term fully dominates already, and 0 by
        construction for non-pipelining schedules (ulysses, window).
        """
        if self.cost is None or self.compute_flops is None:
            return None
        compute_s = self.compute_flops / peak_flops
        seq = self.cost.step_time_s(
            link_bw, compute_s, bidir_links=bidir_links, pipelined=False
        )
        pipe = self.cost.step_time_s(
            link_bw, compute_s, bidir_links=bidir_links,
            pipelined=self.pipelines,
        )
        return {
            "compute_s": compute_s,
            "link_s": self.cost.time_s(link_bw, bidir_links=bidir_links),
            "sequential_s": seq,
            "pipelined_s": pipe,
            "overlap_fraction": (seq - pipe) / seq if seq > 0 else 0.0,
        }

    def __call__(self, *args):
        fn = shard_map(
            self.local_fn, mesh=self.mesh, in_specs=self.in_specs,
            out_specs=self.out_specs, check_vma=False,
        )
        return fn(*args)


@dataclass(frozen=True)
class ParallelContext:
    """Static description of how a model instance is distributed."""

    mesh: Mesh | None = None
    data_axis: str | None = "data"
    sp_axes: tuple[str, ...] = ()  # ("model",) or ("pod", "model")
    strategy: str = "tokenring"
    layout: str = "zigzag"  # zigzag | contig (layout of the seq dim in data)
    impl: str = "auto"  # kernel impl: auto | pallas | pallas_interpret | xla
    block_q: int = 512
    block_k: int = 512
    # Backward kernel tiles (None inherits block_q/block_k); the backward
    # keeps more live tiles per grid step, so these can trade smaller.
    block_q_bwd: int | None = None
    block_k_bwd: int | None = None
    # Decode-path KV tile (None inherits block_k): tunes the dense decode /
    # gather-oracle flash calls.  The fused paged kernel's KV tile is
    # intrinsically the page size, so it ignores this.
    block_k_decode: int | None = None
    inner_strategy: str | None = None  # hybrid inner; defaults to `strategy`
    # Wire format of the traveling (out, lse) accumulator in TokenRing:
    # "bfloat16" halves the per-direction link bytes at ~1e-3 merge rounding
    # (lse always stays fp32).  See benchmarks/bench_comm_volume.py.
    travel_dtype: str = "float32"
    # Whether the fabric carries both ring directions at full rate (TPU ICI,
    # NVLink).  False makes the planner score total bytes, not max-direction.
    bidir_links: bool = True
    # Run step schedules through the double-buffered overlap executor
    # (core/schedule.py).  False serializes every transfer behind the step's
    # compute — bitwise-identical results with the legacy merge→rotate
    # dependency chain, for benchmarking and HLO verification.
    overlap: bool = True

    @property
    def sp_degree(self) -> int:
        if self.mesh is None:
            return 1
        d = 1
        for ax in self.sp_axes:
            d *= self.mesh.shape[ax]
        return d

    @property
    def active(self) -> bool:
        return self.mesh is not None and self.sp_degree > 1

    @property
    def decode_block_k(self) -> int:
        return (
            self.block_k_decode if self.block_k_decode is not None else self.block_k
        )

    def seq_spec(self):
        """PartitionSpec entry for the sequence dimension."""
        if not self.sp_axes:
            return None
        return self.sp_axes if len(self.sp_axes) > 1 else self.sp_axes[0]

    @property
    def flat_axis_name(self):
        """``axis_name`` for collectives over all SP axes jointly: the tuple
        when there are several, the bare name otherwise."""
        return self.sp_axes if len(self.sp_axes) > 1 else self.sp_axes[0]

    # -- planning ----------------------------------------------------------

    def _validate_axes(self) -> None:
        if self.mesh is None:
            raise ValueError("cannot plan without a mesh")
        missing = [ax for ax in self.sp_axes if ax not in self.mesh.axis_names]
        if missing:
            raise ValueError(
                f"sp_axes {missing} not in mesh axes {tuple(self.mesh.axis_names)}"
            )
        if self.data_axis is not None and self.data_axis not in self.mesh.axis_names:
            raise ValueError(
                f"data_axis {self.data_axis!r} not in mesh axes "
                f"{tuple(self.mesh.axis_names)}"
            )
        if not self.sp_axes:
            raise ValueError("planning requires at least one SP axis")

    def _strategy_kwargs(self, desc: SPStrategy) -> dict:
        """Extras declared by the descriptor, sourced from this context."""
        out = {}
        for name in desc.extra_kwargs:
            if hasattr(self, name):
                out[name] = getattr(self, name)
        return out

    def plan(
        self,
        shapes: AttnShapes,
        *,
        causal: bool = True,
        window: int | None = None,
        scale: float | None = None,
        topology=None,
    ) -> ExecutionPlan:
        """Validate mesh/axes/layout and resolve the strategy for these
        shapes, returning the uniform :class:`ExecutionPlan`.

        ``"auto"`` resolves by per-strategy ``comm_cost`` argmin; multi-axis
        meshes get the Case-Study-III hybrid decomposition (inter-pod KV ring
        outside, the resolved strategy inside).

        ``topology`` (a :class:`repro.core.topology.Topology`) arbitrates
        against the physical graph instead of a single abstract link: the
        flat resolved ring is priced at the slowest wire its Hamiltonian
        placement traverses, the hierarchical ``"tokenring2d"`` schedule at
        its per-class ``CommCost.links`` split over the graph's per-class
        bandwidths, and the faster plan wins (``ExecutionPlan
        .topology_decision`` records the scores).  On a pod-structured graph
        with a slow inter-pod fabric the 2D schedule keeps the per-step
        query+accumulator stream on intra-pod wires and wins; on a uniform
        fabric the flat ring's fewer hops win.
        """
        self._validate_axes()
        P_sp = self.sp_degree
        if shapes.Sq % P_sp or shapes.seq_kv % P_sp:
            raise ValueError(
                f"sequence length {shapes.Sq}/{shapes.seq_kv} not divisible "
                f"by SP degree {P_sp}"
            )
        # Cost models are per *device*: the batch dim shards over data.
        B_loc = shapes.B
        if self.data_axis is not None:
            B_loc = max(1, shapes.B // self.mesh.shape[self.data_axis])

        kw = dict(
            causal=causal, window=window, scale=scale, impl=self.impl,
            block_q=self.block_q, block_k=self.block_k,
            block_q_bwd=self.block_q_bwd, block_k_bwd=self.block_k_bwd,
            overlap=self.overlap,
        )

        if topology is not None:
            return self._plan_topology(
                topology, shapes, B_loc=B_loc, causal=causal, window=window,
                kw=kw,
            )

        hybrid = len(self.sp_axes) >= 2
        # Eligibility (and cost) for a hybrid plan is judged at the *inner*
        # ring size: the outer pod axis only circulates KV shards, so e.g.
        # Ulysses' head-divisibility limit applies to the intra-pod degree.
        P_elig = self.mesh.shape[self.sp_axes[-1]] if hybrid else P_sp
        resolve_kw = dict(
            B=B_loc, S=shapes.Sq, Hq=shapes.Hq, Hkv=shapes.Hkv, D=shapes.D,
            bytes_per_elem=shapes.dtype_bytes, S_kv=shapes.seq_kv,
            bidir_links=self.bidir_links, layout=self.layout, window=window,
        )

        # Windowed layers: only window-capable strategies are meaningful —
        # circulating the whole sequence for a local window wastes the ring.
        name = self.strategy
        if window is not None and (
            name == "auto" or not get_strategy(name).supports_window
        ):
            name = resolve_strategy("auto", P=P_sp, **resolve_kw)

        # A hybrid "auto" arbitrates the *inner* schedule: restrict the pool
        # to hybrid-capable strategies up front so the cost argmin is never
        # silently discarded by a post-hoc hybrid_inner_ok fallback.
        candidates = None
        if hybrid and name == "auto":
            from repro.core.strategies import available_strategies

            candidates = tuple(
                n for n in available_strategies() if get_strategy(n).hybrid_inner_ok
            )
        name = resolve_strategy(name, P=P_elig, candidates=candidates, **resolve_kw)
        desc = get_strategy(name)
        if desc.supports_window:
            hybrid = False  # window strategies flatten multi-axis themselves

        compute_flops = attention_compute_flops(
            B_loc, shapes.Sq, shapes.Hq, shapes.D, P_sp,
            S_kv=shapes.seq_kv, causal=causal,
            window=window if desc.supports_window else None,
        )

        dp = self.data_axis
        seq = self.seq_spec()
        qspec = P(dp, seq, None, None)
        pspec = P(dp, seq)
        in_specs = (qspec, qspec, qspec, pspec, pspec)

        if hybrid:
            # Case Study III: inter-pod KV ring outside, `inner` inside.
            from repro.core.hybrid import hybrid_sp

            pod_axis, axis_name = self.sp_axes[0], self.sp_axes[1]
            n_pods = self.mesh.shape[pod_axis]
            P_inner = self.mesh.shape[axis_name]
            inner = self.inner_strategy or name
            inner_desc = get_strategy(inner)
            if not inner_desc.hybrid_inner_ok:
                # Same validation depth whether the intent was expressed via
                # strategy= or inner_strategy= — never silently run a
                # different schedule than the one configured.
                raise ValueError(
                    f"strategy {inner!r} cannot run inside the multi-pod "
                    f"hybrid; pick a hybrid-capable inner (or strategy="
                    f"'auto') for multi-axis meshes"
                )
            why = ineligible_reason(
                inner_desc, Hq=shapes.Hq, Hkv=shapes.Hkv, P=P_inner,
                layout=self.layout, window=window,
            )
            if why is not None:
                raise ValueError(
                    f"hybrid inner strategy {inner!r} cannot run this config "
                    f"(intra-pod degree {P_inner}): {why}"
                )
            inner_extras = self._strategy_kwargs(inner_desc)

            def local_fn(q, k, v, qp, kp):
                return hybrid_sp(
                    q, k, v, qp, kp, pod_axis=pod_axis, axis_name=axis_name,
                    inner=inner, **kw, **inner_extras,
                )

            cost = _hybrid_cost(
                inner_desc, shapes, B_loc=B_loc, n_pods=n_pods,
                P_inner=P_inner, bidir_links=self.bidir_links,
                extras=inner_extras,
            )
            return ExecutionPlan(
                kind="attention", strategy=name, inner=inner, mesh=self.mesh,
                in_specs=in_specs, out_specs=qspec, local_fn=local_fn,
                sp_axes=self.sp_axes, sp_degree=P_sp, cost=cost,
                compute_flops=compute_flops,
                # The outer pod ring always prefetches, but the inner pass
                # dominates the byte count — claim overlap only when the
                # inner schedule can deliver it (ulysses inner cannot).
                pipelines=inner_desc.pipelines,
            )

        # Single flat axis (window strategies flatten multi-axis themselves).
        return self._flat_plan(
            name, shapes, B_loc=B_loc, causal=causal, window=window, kw=kw
        )

    def _flat_plan(
        self,
        name: str,
        shapes: AttnShapes,
        *,
        B_loc: int,
        causal: bool,
        window: int | None,
        kw: dict,
        topo_decision: dict | None = None,
    ) -> ExecutionPlan:
        """Bind ``name`` as one flat ring over all SP axes jointly."""
        desc = get_strategy(name)
        P_sp = self.sp_degree
        why = ineligible_reason(
            desc, Hq=shapes.Hq, Hkv=shapes.Hkv, P=P_sp, layout=self.layout,
            window=window,
        )
        if why is not None:
            raise ValueError(f"strategy {name!r} cannot run this config: {why}")
        extras = self._strategy_kwargs(desc)
        axis_name = self.flat_axis_name
        fn = desc.fn

        def local_fn(q, k, v, qp, kp):
            return fn(q, k, v, qp, kp, axis_name=axis_name, **kw, **extras)

        dp = self.data_axis
        seq = self.seq_spec()
        qspec = P(dp, seq, None, None)
        pspec = P(dp, seq)
        cost = strategy_cost(
            desc, B_loc, shapes.Sq, shapes.Hq, shapes.Hkv, shapes.D, P_sp,
            bytes_per_elem=shapes.dtype_bytes, bidir_links=self.bidir_links,
            S_kv=shapes.seq_kv, window=window, **extras,
        )
        compute_flops = attention_compute_flops(
            B_loc, shapes.Sq, shapes.Hq, shapes.D, P_sp, S_kv=shapes.seq_kv,
            causal=causal, window=window if desc.supports_window else None,
        )
        return ExecutionPlan(
            kind="attention", strategy=name, inner=None, mesh=self.mesh,
            in_specs=(qspec, qspec, qspec, pspec, pspec), out_specs=qspec,
            local_fn=local_fn, sp_axes=self.sp_axes, sp_degree=P_sp,
            cost=cost, compute_flops=compute_flops, pipelines=desc.pipelines,
            topology_decision=topo_decision,
        )

    def _plan_topology(
        self,
        topo,
        shapes: AttnShapes,
        *,
        B_loc: int,
        causal: bool,
        window: int | None,
        kw: dict,
    ) -> ExecutionPlan:
        """Arbitrate flat-vs-hierarchical against a physical topology graph.

        The flat candidate is priced at the slowest wire its Hamiltonian
        ``"ring"`` placement traverses (every hop is one physical wire, so
        the bottleneck link bounds every step); the ``"tokenring2d"``
        candidate at its declared per-class split (``CommCost.links``) over
        the graph's per-class bandwidths — the same two numbers
        ``analysis.topo_check`` certifies against the per-link ledger.
        """
        P_sp = self.sp_degree
        if topo.n_devices != P_sp:
            raise ValueError(
                f"topology {topo.name!r} has {topo.n_devices} devices but "
                f"the mesh's SP degree is {P_sp}"
            )
        resolve_kw = dict(
            B=B_loc, S=shapes.Sq, Hq=shapes.Hq, Hkv=shapes.Hkv, D=shapes.D,
            bytes_per_elem=shapes.dtype_bytes, S_kv=shapes.seq_kv,
            bidir_links=self.bidir_links, layout=self.layout, window=window,
        )
        half_cls = topo.half_duplex_classes()

        # An explicit non-auto pin bypasses arbitration (never silently run
        # a different schedule than the one configured); "auto" resolves the
        # flat candidate by the usual registry argmin first.
        if self.strategy not in ("auto", "tokenring2d"):
            flat = self.strategy
        else:
            flat = resolve_strategy("auto", P=P_sp, **resolve_kw)
        flat_desc = get_strategy(flat)
        flat_cost = strategy_cost(
            flat_desc, B_loc, shapes.Sq, shapes.Hq, shapes.Hkv, shapes.D,
            P_sp, bytes_per_elem=shapes.dtype_bytes,
            bidir_links=self.bidir_links, S_kv=shapes.seq_kv, window=window,
            **self._strategy_kwargs(flat_desc),
        )
        t_flat = flat_cost.time_s(
            {"link": topo.bottleneck_bw()},
            bidir_links=self.bidir_links,
            half_duplex=frozenset({"link"}) if half_cls else frozenset(),
        )
        decision = {
            "topology": topo.name,
            "bottleneck_bw": topo.bottleneck_bw(),
            "class_bandwidths": dict(topo.class_bandwidths()),
            "candidates": {flat: t_flat},
        }

        hier_desc = get_strategy("tokenring2d")
        S_loc = shapes.Sq // P_sp
        eligible_2d = (
            topo.n_pods > 1
            and P_sp % topo.n_pods == 0
            and len(self.sp_axes) == 2
            and self.mesh.shape[self.sp_axes[0]] == topo.n_pods
            and S_loc % 2 == 0
            and window is None
        )
        if self.strategy == "tokenring2d" and not eligible_2d:
            raise ValueError(
                f"strategy 'tokenring2d' cannot run on {topo.name!r} with "
                f"sp_axes {self.sp_axes}: needs a podded graph whose pod "
                f"count equals the first SP axis extent, an even per-rank "
                f"query split, and no window"
            )
        if eligible_2d:
            hier_cost = strategy_cost(
                hier_desc, B_loc, shapes.Sq, shapes.Hq, shapes.Hkv, shapes.D,
                P_sp, bytes_per_elem=shapes.dtype_bytes,
                bidir_links=self.bidir_links, S_kv=shapes.seq_kv,
                window=window, n_pods=topo.n_pods,
                **self._strategy_kwargs(hier_desc),
            )
            t_hier = hier_cost.time_s(
                dict(topo.class_bandwidths()),
                bidir_links=self.bidir_links, half_duplex=half_cls,
            )
            decision["candidates"]["tokenring2d"] = t_hier
            # an explicit flat pin is never overridden — only "auto" (or an
            # explicit 2D pin) binds the hierarchical schedule
            if self.strategy == "tokenring2d" or (
                self.strategy == "auto" and t_hier < t_flat
            ):
                decision["chosen"] = "tokenring2d"
                return self._hier2d_plan(
                    shapes, B_loc=B_loc, causal=causal, kw=kw,
                    cost=hier_cost, decision=decision,
                )
        decision["chosen"] = flat
        return self._flat_plan(
            flat, shapes, B_loc=B_loc, causal=causal, window=window, kw=kw,
            topo_decision=decision,
        )

    def _hier2d_plan(
        self,
        shapes: AttnShapes,
        *,
        B_loc: int,
        causal: bool,
        kw: dict,
        cost: CommCost,
        decision: dict,
    ) -> ExecutionPlan:
        """Bind the hierarchical 2D TokenRing over ``(pod, inner)`` axes."""
        desc = get_strategy("tokenring2d")
        pod_axis, inner_axis = self.sp_axes
        extras = self._strategy_kwargs(desc)
        fn = desc.fn

        def local_fn(q, k, v, qp, kp):
            return fn(
                q, k, v, qp, kp, axis_name=(pod_axis, inner_axis), **kw,
                **extras,
            )

        dp = self.data_axis
        seq = self.seq_spec()
        qspec = P(dp, seq, None, None)
        pspec = P(dp, seq)
        compute_flops = attention_compute_flops(
            B_loc, shapes.Sq, shapes.Hq, shapes.D, self.sp_degree,
            S_kv=shapes.seq_kv, causal=causal,
        )
        return ExecutionPlan(
            kind="attention", strategy="tokenring2d", inner=None,
            mesh=self.mesh, in_specs=(qspec, qspec, qspec, pspec, pspec),
            out_specs=qspec, local_fn=local_fn, sp_axes=self.sp_axes,
            sp_degree=self.sp_degree, cost=cost, compute_flops=compute_flops,
            pipelines=desc.pipelines, topology_decision=decision,
        )

    def _serving_cost(
        self, name: str, shapes: AttnShapes | None,
        table_pages: int | None = None,
    ) -> CommCost | None:
        """Price a registered serving-side schedule for these shapes (the
        same ``comm_cost`` machinery training plans go through).

        ``table_pages`` (per-slot block-table width) adds the paged-cache
        metadata term — see ``decode_comm_cost`` in ``core/decode.py``.
        """
        if shapes is None:
            return None
        B_loc = shapes.B
        if self.data_axis is not None:
            B_loc = max(1, shapes.B // self.mesh.shape[self.data_axis])
        return strategy_cost(
            get_strategy(name), B_loc, shapes.Sq, shapes.Hq, shapes.Hkv,
            shapes.D, self.sp_degree, bytes_per_elem=shapes.dtype_bytes,
            bidir_links=self.bidir_links, S_kv=shapes.seq_kv,
            table_pages=table_pages,
        )

    def plan_decode(
        self,
        *,
        window: int | None = None,
        scale: float | None = None,
        shapes: AttnShapes | None = None,
        table_pages: int | None = None,
    ) -> ExecutionPlan:
        """Decode plan: tiny replicated Q against the sequence-sharded cache.

        Binds the registered ``"decode"`` serving strategy; with ``shapes``
        (``Sq`` = query tokens per step, ``Sk`` = cache capacity) the plan
        carries its modeled per-step link bytes — ``B*Sq*Hq*(D+2)`` fp32
        scalars through a ring all-reduce, independent of the cache length.
        ``table_pages`` prices the paged cache's per-step block-table
        broadcast on top (the K/V pages themselves still never move).
        """
        desc = get_strategy("decode")
        self._validate_axes()
        dp = self.data_axis
        seq = self.seq_spec()
        qspec = P(dp, None, None, None)
        cspec = P(dp, seq, None, None)
        axes = self.sp_axes
        fn = desc.fn

        block_k = self.decode_block_k

        def local_fn(q, kc, vc, kp, qp):
            return fn(
                q, kc, vc, kp, q_pos=qp, axis_names=axes, causal=True,
                window=window, scale=scale, impl=self.impl, block_k=block_k,
            )

        return ExecutionPlan(
            kind="decode", strategy="decode", inner=None, mesh=self.mesh,
            in_specs=(qspec, cspec, cspec, P(dp, seq), P(dp, None)),
            out_specs=qspec, local_fn=local_fn, sp_axes=self.sp_axes,
            sp_degree=self.sp_degree,
            cost=self._serving_cost("decode", shapes, table_pages),
            kernel={
                "path": "dense", "impl": self.impl, "block_k_decode": block_k,
            },
        )

    def plan_decode_paged(
        self,
        *,
        window: int | None = None,
        scale: float | None = None,
        shapes: AttnShapes | None = None,
        table_pages: int | None = None,
    ) -> ExecutionPlan:
        """Fused paged-decode plan: Q replicated, the page pool stays
        page-sharded — **no gathered dense view ever exists**.

        Each shard runs :func:`repro.core.decode.sp_paged_decode_attention`
        over its contiguous page stripe (block tables remapped locally,
        kernel indexes pages through its BlockSpec index maps) and the
        partials merge with the same lse-weighted psum as dense decode —
        identical wire bytes, so the registered ``"decode"`` cost row prices
        this plan too.  ``table_pages`` adds the per-step block-table
        broadcast term exactly as in :meth:`plan_decode`.
        """
        from repro.core.decode import sp_paged_decode_attention

        self._validate_axes()
        dp = self.data_axis
        seq = self.seq_spec()
        qspec = P(dp, None, None, None)
        axes = self.sp_axes
        impl = self.impl
        block_k = self.decode_block_k

        def local_fn(q, k_pool, v_pool, layer, pos_pool, bt, qp, lengths):
            return sp_paged_decode_attention(
                q, k_pool, v_pool, pos_pool, bt, qp, layer, axis_names=axes,
                lengths=lengths, window=window, scale=scale, impl=impl,
                block_k=block_k,
            )

        return ExecutionPlan(
            kind="decode", strategy="decode", inner=None, mesh=self.mesh,
            in_specs=(
                qspec,                           # q (B, 1, Hq, D)
                P(None, seq, None, None, None),  # k pool (L, n_pages, ps, Hkv, D)
                P(None, seq, None, None, None),  # v pool
                P(),                             # layer
                P(seq, None),                    # pos pool (n_pages, ps)
                P(dp, None),                     # block tables (B, W)
                P(dp, None),                     # q_pos (B, 1)
                P(dp),                           # lengths (B,)
            ),
            out_specs=qspec, local_fn=local_fn, sp_axes=self.sp_axes,
            sp_degree=self.sp_degree,
            cost=self._serving_cost("decode", shapes, table_pages),
            kernel={
                "path": "paged_fused", "impl": impl,
                "block_k_decode": block_k,
            },
        )

    def effective_prefill_shapes(
        self, shapes: AttnShapes, *, prefix_hit_rate: float = 0.0
    ) -> AttnShapes:
        """Shapes the prefill arbitration actually prices: the query side
        shrinks to the prefix-cache *miss suffix* (hit pages are already
        resident — only the suffix needs query work), rounded up to an
        SP-degree multiple so a ring schedule could run it; the KV side stays
        the full context (resident prefix KV still participates in
        attention)."""
        if not 0.0 <= prefix_hit_rate <= 1.0:
            raise ValueError(f"prefix_hit_rate {prefix_hit_rate} not in [0, 1]")
        P_sp = self.sp_degree
        miss = shapes.Sq - int(shapes.Sq * prefix_hit_rate)
        Sq_eff = max(P_sp, ceil_div(miss, P_sp) * P_sp)
        return replace(shapes, Sq=Sq_eff, Sk=shapes.seq_kv)

    def choose_prefill_strategy(
        self,
        shapes: AttnShapes,
        *,
        prefix_hit_rate: float = 0.0,
        table_pages: int | None = None,
    ) -> str:
        """Arbitrate the prefill schedule over :data:`PREFILL_CANDIDATES`
        from the KV:Q byte ratio and the measured prefix-cache hit rate.

        ``shapes.Sq`` is the request's query (prompt) length, ``shapes.Sk``
        the full KV context it attends to.  The candidates' ``comm_cost``
        models are evaluated at the miss-suffix query length
        (:meth:`effective_prefill_shapes`): pass-KV scales with the *KV*
        side (right for cold long-KV prefill, where every token's K/V must
        visit every rank anyway), pass-Q and the resident psum scale with
        the *query* side (right once prefix hits collapse it).  Argmin over
        max-direction bytes (total on half-duplex fabrics) with the same
        KV-residency margin the training planner applies — docs/serving.md
        §7 works the crossover.
        """
        self._validate_axes()
        eff = self.effective_prefill_shapes(
            shapes, prefix_hit_rate=prefix_hit_rate
        )
        P_sp = self.sp_degree
        B_loc = eff.B
        if self.data_axis is not None:
            B_loc = max(1, eff.B // self.mesh.shape[self.data_axis])
        extras = {
            "travel_dtype": _decision_travel_dtype(eff.dtype_bytes),
            "table_pages": table_pages,
        }
        scored = []
        for name in PREFILL_CANDIDATES:
            desc = get_strategy(name)
            cost = strategy_cost(
                desc, B_loc, eff.Sq, eff.Hq, eff.Hkv, eff.D, P_sp,
                bytes_per_elem=eff.dtype_bytes, bidir_links=self.bidir_links,
                S_kv=eff.seq_kv, **extras,
            )
            score = cost.max_direction if self.bidir_links else cost.total
            scored.append((score, desc))
        scored.sort(key=lambda t: (t[0], t[1].name))
        best_score = scored[0][0]
        for score, desc in scored:
            if desc.kv_resident and score <= KV_RESIDENT_MARGIN * best_score:
                return desc.name
        return scored[0][1].name

    def plan_prefill(
        self,
        *,
        window: int | None = None,
        scale: float | None = None,
        shapes: AttnShapes | None = None,
        table_pages: int | None = None,
        strategy: str | None = None,
        prefix_hit_rate: float = 0.0,
    ) -> ExecutionPlan:
        """Chunked-prefill plan: a replicated prompt chunk against the
        resident sharded cache plus its own local block (cross-chunk
        causality via the Update() merge — see ``core/decode.py``).

        ``strategy=None`` (the default) binds the registered ``"prefill"``
        serving strategy; with ``shapes`` (``Sq`` = chunk length, ``Sk`` =
        cache capacity) the plan carries the modeled per-chunk link bytes
        (plus the paged block-table term when ``table_pages`` is given).

        ``strategy="auto"`` arbitrates per request over
        :data:`PREFILL_CANDIDATES` via :meth:`choose_prefill_strategy`
        (requires ``shapes``; ``prefix_hit_rate`` is the engine's measured
        cross-request prefix-cache hit rate, ``serving/engine.py``).  A ring
        winner returns an *attention-style* plan — q/k/v sequence-sharded
        over the SP axes, causal — over the miss-suffix shapes; the psum
        winner returns the resident-chunk plan below.  An explicit ring name
        binds that ring unconditionally.
        """
        if strategy is not None:
            if strategy == "auto":
                if shapes is None:
                    raise ValueError(
                        "plan_prefill(strategy='auto') needs shapes= to "
                        "arbitrate the KV:Q byte ratio"
                    )
                strategy = self.choose_prefill_strategy(
                    shapes, prefix_hit_rate=prefix_hit_rate,
                    table_pages=table_pages,
                )
            elif strategy not in PREFILL_CANDIDATES:
                raise ValueError(
                    f"plan_prefill strategy {strategy!r} not one of "
                    f"{PREFILL_CANDIDATES}"
                )
            if strategy != "prefill":
                return self._plan_prefill_ring(
                    strategy, shapes, window=window, scale=scale,
                    prefix_hit_rate=prefix_hit_rate,
                )
        desc = get_strategy("prefill")
        self._validate_axes()
        dp = self.data_axis
        seq = self.seq_spec()
        qspec = P(dp, None, None, None)
        cspec = P(dp, seq, None, None)
        axes = self.sp_axes
        fn = desc.fn

        def local_fn(q, kn, vn, np_, kc, vc, kp, qp):
            return fn(
                q, kn, vn, np_, kc, vc, kp, axis_names=axes, q_pos=qp,
                window=window, scale=scale, impl=self.impl,
                block_q=self.block_q, block_k=self.block_k,
            )

        return ExecutionPlan(
            kind="prefill", strategy="prefill", inner=None, mesh=self.mesh,
            in_specs=(
                qspec, qspec, qspec, P(dp, None),  # chunk q/k/v + positions
                cspec, cspec, P(dp, seq),          # resident cache + positions
                P(dp, None),                       # q_pos
            ),
            out_specs=qspec, local_fn=local_fn, sp_axes=self.sp_axes,
            sp_degree=self.sp_degree,
            cost=self._serving_cost("prefill", shapes, table_pages),
        )

    def _plan_prefill_ring(
        self,
        name: str,
        shapes: AttnShapes | None,
        *,
        window: int | None,
        scale: float | None,
        prefix_hit_rate: float = 0.0,
    ) -> ExecutionPlan:
        """Bind a prefill *ring* (``passkv_ring`` / ``passq_ring``) as an
        attention-style plan over the miss-suffix shapes: q/k/v enter
        sequence-sharded over the SP axes (unlike the resident-chunk path's
        replicated chunk), the ring circulates its chosen side, causal."""
        self._validate_axes()
        desc = get_strategy(name)
        P_sp = self.sp_degree
        axis_name = self.flat_axis_name
        extras = self._strategy_kwargs(desc)
        kw = dict(
            causal=True, window=window, scale=scale, impl=self.impl,
            block_q=self.block_q, block_k=self.block_k,
            block_q_bwd=self.block_q_bwd, block_k_bwd=self.block_k_bwd,
            overlap=self.overlap,
        )
        fn = desc.fn

        def local_fn(q, k, v, qp, kp):
            return fn(q, k, v, qp, kp, axis_name=axis_name, **kw, **extras)

        dp = self.data_axis
        seq = self.seq_spec()
        qspec = P(dp, seq, None, None)
        pspec = P(dp, seq)
        cost = None
        compute_flops = None
        if shapes is not None:
            eff = self.effective_prefill_shapes(
                shapes, prefix_hit_rate=prefix_hit_rate
            )
            B_loc = eff.B
            if dp is not None:
                B_loc = max(1, eff.B // self.mesh.shape[dp])
            cost = strategy_cost(
                desc, B_loc, eff.Sq, eff.Hq, eff.Hkv, eff.D, P_sp,
                bytes_per_elem=eff.dtype_bytes, bidir_links=self.bidir_links,
                S_kv=eff.seq_kv, window=window, **extras,
            )
            compute_flops = attention_compute_flops(
                B_loc, eff.Sq, eff.Hq, eff.D, P_sp, S_kv=eff.seq_kv,
                causal=True,
            )
        return ExecutionPlan(
            kind="prefill", strategy=name, inner=None, mesh=self.mesh,
            in_specs=(qspec, qspec, qspec, pspec, pspec), out_specs=qspec,
            local_fn=local_fn, sp_axes=self.sp_axes, sp_degree=P_sp,
            cost=cost, compute_flops=compute_flops, pipelines=desc.pipelines,
        )

    def plan_scan(self, *, ndim: int, axis: int = 1) -> ExecutionPlan:
        """Sequence-parallel linear-recurrence plan (contiguous layout)."""
        from repro.core.recurrence import chunked_linear_recurrence

        self._validate_axes()
        spec_entries = [self.data_axis] + [None] * (ndim - 1)
        spec_entries[axis] = self.seq_spec()
        spec = P(*spec_entries)
        axis_name = self.flat_axis_name

        def local_fn(a, b):
            return chunked_linear_recurrence(a, b, axis_name=axis_name, axis=axis)

        return ExecutionPlan(
            kind="scan", strategy=None, inner=None, mesh=self.mesh,
            in_specs=(spec, spec), out_specs=spec, local_fn=local_fn,
            sp_axes=self.sp_axes, sp_degree=self.sp_degree,
        )


def _hybrid_cost(
    inner_desc: SPStrategy,
    shapes: AttnShapes,
    *,
    B_loc: int,
    n_pods: int,
    P_inner: int,
    bidir_links: bool,
    extras: dict,
) -> CommCost:
    """Case-Study-III accounting: every pod step each device forwards its
    *device-local* KV shard (S_kv / (n_pods * P_inner) rows — see
    core/hybrid.py) over the slow axis, and runs a full inner pass over the
    fast axis."""
    S_kv = shapes.seq_kv
    kv_shard = (
        2 * B_loc * (S_kv // (n_pods * P_inner)) * shapes.Hkv * shapes.D
        * shapes.dtype_bytes
    )
    outer = CommCost((n_pods - 1) * kv_shard, 0.0)
    inner = strategy_cost(
        inner_desc, B_loc, shapes.Sq // n_pods, shapes.Hq, shapes.Hkv,
        shapes.D, P_inner, bytes_per_elem=shapes.dtype_bytes,
        bidir_links=bidir_links, S_kv=S_kv // n_pods, **extras,
    )
    return CommCost(
        outer.fwd_bytes + n_pods * inner.fwd_bytes,
        outer.bwd_bytes + n_pods * inner.bwd_bytes,
    )


def choose_strategy(strategy: str, Hq: int, Hkv: int, P_sp: int) -> str:
    """Back-compat shim for the pre-registry chooser: arbitrates the ring
    family (TokenRing vs bidirectional KV ring) from head counts alone by
    evaluating the registered ``comm_cost`` models at a representative shape.
    Prefer :func:`repro.core.strategies.resolve_strategy` (full shape/topology
    arbitration over every registered strategy).
    """
    if strategy != "auto":
        get_strategy(strategy)
        return strategy
    return resolve_strategy(
        "auto", S=1024 * max(P_sp, 1), Hq=Hq, Hkv=Hkv, D=128, P=P_sp,
        bytes_per_elem=2, candidates=("tokenring", "ring_bidir"),
    )


def sp_attention(
    q,
    k,
    v,
    q_pos,
    k_pos,
    *,
    pctx: ParallelContext,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
):
    """Sequence-parallel attention on global arrays.

    ``q (B,Sq,Hq,D)``, ``k/v (B,Sk,Hkv,D)``, ``q_pos (B,Sq)``/``(Sq,)``,
    ``k_pos (B,Sk)``/``(Sk,)`` global token positions (already
    layout-permuted, e.g. zigzag; per-batch rows support continuous batching).
    """
    from repro.kernels.ops import flash_attention
    from repro.kernels.ref import normalize_positions

    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    q_pos = normalize_positions(q_pos, B, Sq)
    k_pos = normalize_positions(k_pos, B, Sk)

    if not pctx.active:
        out, _ = flash_attention(
            q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal, window=window,
            scale=scale, impl=pctx.impl, block_q=pctx.block_q,
            block_k=pctx.block_k, block_q_bwd=pctx.block_q_bwd,
            block_k_bwd=pctx.block_k_bwd,
        )
        return out

    shapes = AttnShapes(
        B=B, Sq=Sq, Hq=Hq, Hkv=Hkv, D=D, Sk=Sk,
        dtype_bytes=jnp.dtype(q.dtype).itemsize,
    )
    plan = pctx.plan(shapes, causal=causal, window=window, scale=scale)
    return plan(q, k, v, q_pos, k_pos)


def sp_decode(
    q,
    k_cache,
    v_cache,
    k_pos,
    q_pos,
    *,
    pctx: ParallelContext,
    window: int | None = None,
    scale: float | None = None,
    table_pages: int | None = None,
):
    """Sequence-parallel decode: tiny Q replicated, cache stays sharded.

    ``q (B,Sq,Hq,D)`` (Sq small), caches ``(B,Skv,Hkv,D)`` sharded over the SP
    axes on dim 1, ``k_pos (B,Skv)`` (PAD_POS sentinel for unwritten slots),
    ``q_pos (B,Sq)`` — per-request rows support continuous batching.
    ``table_pages``: block-table width when the cache arrays are gathered
    page views (paged serving) — priced into the plan's cost term.
    """
    from repro.kernels.ops import flash_attention
    from repro.kernels.ref import normalize_positions

    B = q.shape[0]
    q_pos = normalize_positions(q_pos, B, q.shape[1])
    k_pos = normalize_positions(k_pos, B, k_cache.shape[1])

    if not pctx.active:
        out, _ = flash_attention(
            q, k_cache, v_cache, q_pos=q_pos, k_pos=k_pos, causal=True,
            window=window, scale=scale, impl=pctx.impl, block_k=pctx.block_k,
        )
        return out

    shapes = AttnShapes(
        B=B, Sq=q.shape[1], Hq=q.shape[2], Hkv=k_cache.shape[2], D=q.shape[3],
        Sk=k_cache.shape[1], dtype_bytes=jnp.dtype(q.dtype).itemsize,
    )
    plan = pctx.plan_decode(
        window=window, scale=scale, shapes=shapes, table_pages=table_pages
    )
    return plan(q, k_cache, v_cache, k_pos, q_pos)


def sp_decode_paged(
    q,
    k_pool,
    v_pool,
    layer,
    pos_pool,
    block_tables,
    q_pos,
    lengths,
    *,
    pctx: ParallelContext,
    window: int | None = None,
    scale: float | None = None,
    table_pages: int | None = None,
):
    """Fused paged decode on global arrays: no materialized KV gather.

    ``q (B, 1, Hq, D)`` replicated over the SP axes; the stacked pools
    ``k_pool``/``v_pool (L, n_pages, page_size, Hkv, D)``, read at ``layer``
    (int32 scalar), and ``pos_pool (n_pages, page_size)`` page-sharded;
    ``block_tables (B, W)`` int32
    (``n_pages`` sentinel for unmapped entries), ``q_pos (B, 1)`` and
    ``lengths (B,)`` used lengths (clamps the xla oracle's gathered view —
    the fused kernel masks by the pos pool's PAD sentinel instead).
    Dispatches on ``pctx.impl`` inside: pallas / pallas_interpret run the
    fused kernel of ``kernels/paged_attention.py``, xla the gather oracle.
    """
    from repro.core.decode import sp_paged_decode_attention

    if not pctx.active:
        return sp_paged_decode_attention(
            q, k_pool, v_pool, pos_pool, block_tables, q_pos, layer, axis_names=(),
            lengths=lengths, window=window, scale=scale, impl=pctx.impl,
            block_k=pctx.decode_block_k,
        )

    shapes = AttnShapes(
        B=q.shape[0], Sq=q.shape[1], Hq=q.shape[2], Hkv=k_pool.shape[3],
        D=q.shape[3], Sk=k_pool.shape[1] * k_pool.shape[2],
        dtype_bytes=jnp.dtype(q.dtype).itemsize,
    )
    plan = pctx.plan_decode_paged(
        window=window, scale=scale, shapes=shapes, table_pages=table_pages
    )
    return plan(q, k_pool, v_pool, layer, pos_pool, block_tables, q_pos, lengths)


def sp_prefill(
    q,
    k_new,
    v_new,
    new_pos,
    k_cache,
    v_cache,
    k_pos,
    q_pos,
    *,
    pctx: ParallelContext,
    window: int | None = None,
    scale: float | None = None,
    table_pages: int | None = None,
):
    """Sequence-parallel chunked-prefill attention on global arrays.

    ``q``/``k_new``/``v_new (B,C,H,D)`` and ``new_pos``/``q_pos (B,C)`` are
    the prompt chunk (replicated over the SP axes); ``k_cache``/``v_cache
    (B,Skv,Hkv,D)`` and ``k_pos (B,Skv)`` the resident cache holding every
    *previous* chunk (sharded over the SP axes on dim 1, PAD_POS sentinel for
    unwritten slots).  The chunk's K/V must be written into the cache by the
    caller *after* this call — the chunk block is attended locally and merged
    with the cache partial via the Update() equations (``core/decode.py``).
    """
    from repro.core.decode import sp_prefill_chunk_attention
    from repro.kernels.ref import normalize_positions

    B, C = q.shape[0], q.shape[1]
    q_pos = normalize_positions(q_pos, B, C)
    new_pos = normalize_positions(new_pos, B, C)
    k_pos = normalize_positions(k_pos, B, k_cache.shape[1])

    if not pctx.active:
        return sp_prefill_chunk_attention(
            q, k_new, v_new, new_pos, k_cache, v_cache, k_pos,
            axis_names=(), q_pos=q_pos, window=window, scale=scale,
            impl=pctx.impl, block_q=pctx.block_q, block_k=pctx.block_k,
        )

    shapes = AttnShapes(
        B=B, Sq=C, Hq=q.shape[2], Hkv=k_cache.shape[2], D=q.shape[3],
        Sk=k_cache.shape[1], dtype_bytes=jnp.dtype(q.dtype).itemsize,
    )
    plan = pctx.plan_prefill(
        window=window, scale=scale, shapes=shapes, table_pages=table_pages
    )
    return plan(q, k_new, v_new, new_pos, k_cache, v_cache, k_pos, q_pos)


def sp_scan(a, b, *, pctx: ParallelContext, axis: int = 1):
    """Sequence-parallel diagonal linear recurrence on global arrays.

    Requires ``layout="contig"`` semantics on the sequence dim (recurrences
    are order-sensitive; zigzag does not apply — see DESIGN.md).
    """
    if not pctx.active:
        from repro.core.recurrence import local_linear_recurrence

        h, _ = local_linear_recurrence(a, b, axis=axis)
        return h

    plan = pctx.plan_scan(ndim=a.ndim, axis=axis)
    return plan(a, b)
