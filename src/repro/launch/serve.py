"""Serving launcher: batched requests through the continuous-batching engine.

Prompts prefill in fixed-size chunks through the model's fused
``prefill_chunk`` step (``--prefill-chunk`` tokens per step, interleaved
with decode under ``--token-budget``); decode runs the resident-cache
lse-merge psum.  Both schedules are registered strategies — the launcher
prints their planner-modeled per-step link bytes for the served config next
to the measured throughput (the serving analog of ``launch/dryrun``'s plan
record).

``--page-size`` switches the KV cache from dense per-slot slabs to the
paged pool (``serving/kv_cache.py``): admission by free pages, page-granular
decode growth, and (``--preempt``) recompute-style eviction when
``--max-pages`` runs dry — see docs/serving.md §6.

``--fault-rate`` turns on the resilience runtime's chaos injector
(``serving/resilience.py``): every engine tick point fails with that
probability, exercised through quarantine/retry, the degrade ladder, and
the cache auditor; ``--snapshot-dir``/``--snapshot-every`` add periodic
serving-state snapshots restartable via ``ServingEngine.from_snapshot``
— see docs/resilience.md.

Example (CPU, reduced model, 16 batched requests, paged):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --requests 16 --max-new 24 --prefill-chunk 16 --token-budget 32 \
      --page-size 16 --max-pages 24
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS
from repro.core.api import ParallelContext
from repro.core.strategies import get_strategy, strategy_cost
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import ServingEngine


def print_serving_plan(cfg, *, max_batch: int, chunk: int, max_len: int,
                       sp_degree: int = 4, page_size: int | None = None,
                       prefix_hit_rate: float | None = None):
    """Planner view of the serving schedules for this config: modeled
    per-step link bytes at an SP degree of ``sp_degree`` (the same
    ``comm_cost`` models ``plan_decode`` / ``plan_prefill`` attach to real
    multi-device plans).  With ``page_size`` the paged block-table term
    rides along (``table_pages = ceil(max_len / page_size)``).  With a
    ``prefix_hit_rate`` (measured by the engine's prefix index) the adaptive
    prefill arbitration is printed too: which of the prefill candidates —
    resident psum chunks, pass-KV ring, pass-Q ring — the planner would bind
    for a full-length prompt at that hit rate (docs/serving.md §7)."""
    from repro.serving.kv_cache import pages_for

    bpe = 2 if cfg.dtype == "bfloat16" else 4
    table_pages = pages_for(max_len, page_size) if page_size else None
    common = dict(bytes_per_elem=bpe, S_kv=max_len, table_pages=table_pages)
    dec = strategy_cost(
        get_strategy("decode"), max_batch, 1, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, sp_degree, **common,
    )
    pre = strategy_cost(
        get_strategy("prefill"), 1, chunk, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, sp_degree, **common,
    )
    paged = (
        f" (paged: +{table_pages}-entry block table/slot)" if page_size else ""
    )
    print(
        f"serving plan @ SP={sp_degree}: decode {dec.max_direction:.0f} B/step "
        f"(batch {max_batch}), prefill {pre.max_direction:.0f} B/chunk "
        f"(chunk {chunk}) — cache-resident, independent of context length"
        f"{paged}"
    )
    if prefix_hit_rate is not None:
        print_adaptive_prefill(
            cfg, max_len=max_len, sp_degree=sp_degree,
            table_pages=table_pages, prefix_hit_rate=prefix_hit_rate,
        )


def print_adaptive_prefill(cfg, *, max_len: int, sp_degree: int = 4,
                           table_pages: int | None = None,
                           prefix_hit_rate: float = 0.0):
    """The prefill-ring arbitration for a full-length prompt at the
    engine's *measured* prefix-cache hit rate: which of ``prefill`` (the
    resident psum chunk path), ``passkv_ring``, ``passq_ring`` the planner
    would bind next (``ParallelContext.choose_prefill_strategy``; the byte
    crossover is worked in docs/serving.md §7)."""
    import jax as _jax

    from repro.core.api import AttnShapes

    pctx = ParallelContext(
        mesh=_jax.sharding.AbstractMesh((("sp", sp_degree),)),
        sp_axes=("sp",), data_axis=None,
    )
    shp = AttnShapes(
        B=1, Sq=max_len, Hq=cfg.n_heads, Hkv=cfg.n_kv_heads,
        D=cfg.head_dim, dtype_bytes=2 if cfg.dtype == "bfloat16" else 4,
    )
    cold = pctx.choose_prefill_strategy(shp, table_pages=table_pages)
    warm = pctx.choose_prefill_strategy(
        shp, prefix_hit_rate=prefix_hit_rate, table_pages=table_pages
    )
    print(
        f"adaptive prefill @ SP={sp_degree}: cold -> {cold}, "
        f"measured hit rate {prefix_hit_rate:.2f} -> {warm}"
    )


def main(argv=None):
    """Serve ``--requests`` synthetic requests; returns ``(stats, finished
    requests)`` — each request carries its generated ``output`` tokens."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens per chunked-prefill step")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="prefill tokens per iteration are capped at this "
                    "minus the number of decoding slots (decode itself is "
                    "indivisible: one token per decoding slot either way)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="enable the paged KV cache with this many tokens "
                    "per page (default: dense per-slot slab)")
    ap.add_argument("--max-pages", type=int, default=None,
                    help="page-pool size; defaults to the dense-equivalent "
                    "max_batch * ceil(max_len/page_size) — size it below "
                    "that to stop pinning worst-case memory")
    ap.add_argument("--preempt", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="evict the newest request (recompute-style) when "
                    "the page pool runs dry instead of raising")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="content-addressed prefix reuse across requests "
                    "(paged cache only): requests sharing a prompt prefix "
                    "map the same physical pages and prefill skips straight "
                    "to the miss suffix")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens to "
                    "every request (exercises the prefix cache)")
    ap.add_argument("--impl", default="auto",
                    choices=("auto", "pallas", "pallas_interpret", "xla"),
                    help="attention kernel impl: pallas runs the fused "
                    "paged-decode kernel (block-table indexing in the index "
                    "maps, no gathered KV view); xla keeps the dense-gather "
                    "oracle; pallas_interpret runs the kernel in interpreter "
                    "mode on CPU (docs/kernels.md)")
    ap.add_argument("--block-k-decode", type=int, default=None,
                    help="KV tile for the *dense* decode flash kernel "
                    "(the paged kernel tiles by page; this knob also rides "
                    "into FlashConfig.block_k_decode for plan records)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos mode: every engine tick point (admit/"
                    "prefill/decode/alloc/evict/cow/sample) fails with this "
                    "probability; quarantine/retry + the degrade ladder keep "
                    "the batch serving (docs/resilience.md)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for --fault-rate's injector (reproducible)")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the cache-invariant auditor every N engine "
                    "ticks (0 = only after recoveries)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="per-request quarantine/retry budget before a "
                    "request is failed permanently")
    ap.add_argument("--retry-backoff", type=int, default=1,
                    help="base of the exponential re-admission backoff, "
                    "in engine ticks")
    ap.add_argument("--snapshot-dir", default=None,
                    help="serving-state snapshot directory (enables "
                    "ServingEngine.from_snapshot restart)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot the engine every N ticks while requests "
                    "are in flight (needs --snapshot-dir)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    pctx = ParallelContext(
        mesh=None, impl=args.impl, block_k_decode=args.block_k_decode
    )
    bundle = build_model(cfg, pctx)
    params = bundle.init(jax.random.PRNGKey(args.seed))

    print_serving_plan(
        cfg, max_batch=args.max_batch, chunk=args.prefill_chunk,
        max_len=args.max_len, page_size=args.page_size,
    )
    plan = None
    if args.fault_rate:
        from repro.serving.resilience import FaultPlan

        plan = FaultPlan.bernoulli(args.fault_rate, seed=args.fault_seed)
    eng = ServingEngine(
        bundle, params, max_batch=args.max_batch, max_len=args.max_len,
        temperature=args.temperature, seed=args.seed,
        prefill_chunk=args.prefill_chunk, token_budget=args.token_budget,
        page_size=args.page_size, max_pages=args.max_pages,
        preempt=args.preempt, prefix_cache=args.prefix_cache,
        fault_plan=plan, audit_every=args.audit_every,
        max_retries=args.max_retries, retry_backoff=args.retry_backoff,
        snapshot_dir=args.snapshot_dir, snapshot_every=args.snapshot_every,
    )
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size, args.shared_prefix)
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(3, 9))
        prompt = np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, plen)]
        ).astype(np.int32)
        eng.submit(prompt, max_new_tokens=args.max_new)
    done = eng.run()
    dt = time.perf_counter() - t0
    s = eng.stats()
    print(
        f"served {s['requests']} requests, {s['tokens']} tokens in {dt:.2f}s "
        f"({s['tokens']/dt:.1f} tok/s) mean_latency {s['mean_latency_s']*1e3:.0f} ms "
        f"mean_ttft {s['mean_ttft_s']*1e3:.0f} ms"
    )
    print(
        f"steps: {s['decode_steps']} decode, {s['prefill_steps']} prefill "
        f"chunks ({s['prefill_tokens']} prompt tokens)"
    )
    if "pages" in s:
        u = s["pages"]
        print(
            f"pages: {u['high_water']}/{u['pages_total']} high-water "
            f"(x{args.page_size} tokens), {s['preemptions']} preemptions"
        )
    if "prefix" in s:
        p = s["prefix"]
        print(
            f"prefix cache: {p['hit_tokens']}/{p['lookup_tokens']} tokens hit "
            f"({p['hit_rate']*100:.0f}%), {p['indexed_pages']} pages indexed, "
            f"{p['cow_copies']} COW copies, {p['evictions']} evictions"
        )
        # Thread the *measured* hit rate back into the planner: the prefill
        # schedule the arbitration would bind for the next such request.
        from repro.serving.kv_cache import pages_for

        print_adaptive_prefill(
            cfg, max_len=args.max_len,
            table_pages=pages_for(args.max_len, args.page_size),
            prefix_hit_rate=p["hit_rate"],
        )
    if s["faults"] or s["snapshots"] or args.audit_every:
        d, st = s["degrade"], s["step_time"]
        print(
            f"resilience: {s['faults']} faults, {s['recoveries']} recoveries, "
            f"{s['quarantines']} quarantines, {s['failed_requests']} failed, "
            f"{s['load_shed']} shed; ladder {d['mode']} "
            f"({d['escalations']} escalations); {s['snapshots']} snapshots; "
            f"step median {st['median_s']*1e3:.1f} ms "
            f"({st['straggler_events']} straggler events)"
        )
    for r in done[:3]:
        tail = r.prompt[-8:].tolist()
        print(f"  req {r.uid}: prompt of {len(r.prompt)} tokens, ending "
              f"{tail} -> {r.output}")
    return s, done


if __name__ == "__main__":
    main()
