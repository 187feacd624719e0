"""Serving cells: an open loop of requests through ``ServingEngine``.

The window submits each request when it is due, whether or not the engine
has caught up, and drives the engine one tick (``run(max_steps=1)``) at a
time, stamping every request's tokens after each tick.  Time to first token
runs from the request's due time, so a stall delays every request behind it.

Correctness: once the window has closed, a sample of the finished requests
drawn from the seed, the longest among them, goes through the plain float32
reference (``reference/qwen3.py``) over its prompt and served tokens.  The
compared number is the widest gap by which a served token's reference logit
lies below the reference's best logit at that position (tokens are greedy).
"""

from __future__ import annotations

import gc
import time
from collections import Counter

import numpy as np

from bench import harness, roofline, traffic, weights
from bench.harness import Outcome, span

SAMPLE_TOKENS = 256  # served tokens the reference checks, at least
SAMPLE_MAX_REQUESTS = 8


def arch_config(c: dict):
    """The program's ``ArchConfig`` for a Qwen3 dense configuration file."""
    from repro.models.config import ArchConfig

    if c["model_type"] != "qwen3":
        raise ValueError(f"serve driver runs qwen3 configurations, not {c['model_type']!r}")
    return ArchConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=c["attention_bias"], qk_norm=True, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), tie_embeddings=c["tie_word_embeddings"],
        dtype=c["torch_dtype"], param_dtype=c["torch_dtype"],
    )


def program_params(W: dict, abstract) -> dict:
    """The reference's weights under the program's parameter names; the
    shapes must match the program's own ``init``."""
    import jax

    L = W["layers"]
    tree = {
        "embed": {"table": W["embed"]},
        "final_norm": {"scale": W["final_norm"]},
        "layers": {
            "attn": {
                "wq": {"w": L["wq"]}, "wk": {"w": L["wk"]}, "wv": {"w": L["wv"]},
                "wo": {"w": L["wo"]},
                "q_norm": {"scale": L["q_norm"]}, "k_norm": {"scale": L["k_norm"]},
            },
            "ln1": {"scale": L["ln1"]}, "ln2": {"scale": L["ln2"]},
            "mlp": {"gate": {"w": L["gate"]}, "up": {"w": L["up"]}, "down": {"w": L["down"]}},
        },
    }
    want = jax.tree_util.tree_map(lambda a: tuple(a.shape), abstract)
    got = jax.tree_util.tree_map(lambda a: tuple(a.shape), tree)
    if want != got:
        raise ValueError(f"program parameter tree changed: {want} != {got}")
    return tree


def warm_up(engine, vocab: int, page: int, chunk: int, rounds: int = 2):
    """Compile every program the window runs, in every argument state it
    meets there: admission into a fresh and into a used engine, chunked
    prefill (whole and partial chunks), decode that maps a new page, slot
    reset, page release and sampling.  Prompts end one token short of a page
    boundary, so the third decode step crosses into a new page.  A request
    alone with three chunks of prompt prefills with an unchanged block table
    after its first chunk; the staggered rounds cover the rest."""
    import jax

    rng = np.random.default_rng(0)
    solo = engine.submit(rng.integers(0, vocab, 3 * chunk, dtype=np.int32), max_new_tokens=2)
    while solo.status not in ("done", "failed"):
        engine.run(max_steps=1)
    for _ in range(rounds):
        reqs = []
        for pages in (2, 3, 1, 4):
            prompt = rng.integers(0, vocab, pages * page - 1, dtype=np.int32)
            reqs.append(engine.submit(prompt, max_new_tokens=4))
            engine.run(max_steps=1)
        while any(r.status not in ("done", "failed") for r in reqs):
            engine.run(max_steps=1)
    jax.block_until_ready(engine.state)


class Live:
    """One request of the run, as the harness saw it."""

    __slots__ = ("req", "due", "submitted", "n", "t_first", "stamps")

    def __init__(self, req, due, submitted):
        self.req, self.due, self.submitted = req, due, submitted
        self.n = 0
        self.t_first = None
        self.stamps = []  # (time, tokens so far) after each tick that added some


def _filled(req):
    # Prompt tokens the engine has written to the cache: read to count each
    # step's prefill work for the roofline and MFU readers only.
    return getattr(req, "_filled", 0)


class Served:
    """Set-up shared by every window of a process: model, weights, engine."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.core.api import ParallelContext
        from repro.models import build_model

        self.c, self.s = ctx.config, ctx.config["serving"]
        self.dev = ctx.devices[0]
        self.bundle = build_model(arch_config(self.c),
                                  ParallelContext(mesh=None, impl=self.s["impl"]))
        self.W = weights.make(ctx.seed, self.c, dtype=jnp.dtype(self.c["torch_dtype"]),
                              device=self.dev)
        self.params = program_params(
            self.W, jax.eval_shape(self.bundle.init, jax.random.PRNGKey(0)))

    def engine(self):
        from repro.serving.engine import ServingEngine

        s = self.s
        eng = ServingEngine(
            self.bundle, self.params, max_batch=s["max_batch"], max_len=s["max_len"],
            prefill_chunk=s["prefill_chunk"], page_size=s["page_size"],
            max_pages=s["max_pages"], preempt=s["preempt"],
        )
        warm_up(eng, self.c["vocab_size"], s["page_size"], s["prefill_chunk"])
        return eng


def drive(engine, schedule, seconds, prof=None, trace_at=(0.0, 0.0)):
    """Submit each request when due and tick the engine until the window
    closes.  Returns the record."""
    import jax

    live, ticks, queue = [], [], []
    pages_peak = 0
    i_next = 0
    counter = harness.CompileCounter()
    counter.active = True
    t_start = time.perf_counter()
    t_end = t_start + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while i_next < len(schedule) and t_start + schedule[i_next].due_s <= now:
            r = schedule[i_next]
            with span("submit"):
                h = engine.submit(r.prompt, max_new_tokens=r.max_new)
            live.append(Live(h, t_start + r.due_s, time.perf_counter()))
            i_next += 1
        if prof is not None and not prof.on and prof.t_stop is None and now - t_start >= trace_at[0]:
            jax.block_until_ready(engine.state)
            prof.start()
        elif prof is not None and prof.on and now - t_start >= trace_at[1]:
            with span("engine_tick"):  # the tail of the last traced tick
                jax.block_until_ready(engine.state)
            prof.stop()
        inflight = [x for x in live if x.req.status not in ("done", "failed")]
        if not inflight:
            nxt = t_start + schedule[i_next].due_s if i_next < len(schedule) else t_end
            time.sleep(max(min(nxt, t_end) - time.perf_counter(), 0.0))
            continue
        before = [(x, _filled(x.req), len(x.req.output)) for x in inflight]
        with span("engine_tick"):
            engine.run(max_steps=1)
        t = time.perf_counter()
        pages_peak = max(pages_peak, engine.alloc.pages_in_use)
        queue.append((t - t_start, sum(1 for x in live if x.req.status == "queued")))
        rows, ctxs = [], []
        for x, f0, n0 in before:
            n = len(x.req.output)
            if n > x.n:
                if x.n == 0:
                    x.t_first = t
                x.n = n
                x.stamps.append((t, n))
            f1 = _filled(x.req)
            if f1 != f0:
                rows.append((f0, f1 - f0) if f1 > f0 else (0, f1))
            if n > n0:
                ctxs.append(len(x.req.prompt) + n - 1)
        if prof is not None and prof.on:
            ticks.append({"prefill_rows": rows, "decode_contexts": ctxs})
    counter.active = False
    if prof is not None and prof.on:
        with span("engine_tick"):
            jax.block_until_ready(engine.state)
        prof.stop()
    counter.close()
    if not any(hasattr(x.req, "_filled") for x in live):
        for tk in ticks:  # the engine no longer says how far prefill got
            tk["prefill_rows"] = None
    return {
        "live": live, "ticks": ticks, "queue": queue, "pages_peak": pages_peak,
        "t_start": t_start, "t_end": t_end, "compiles": counter.count,
        "unsent": [t_start + r.due_s for r in schedule[i_next:] if r.due_s < seconds],
    }


def window_metrics(w: dict, seconds: float) -> tuple:
    """End-to-end metrics of one window, over every request due in it.  A
    request that fell due while a tick ran past the close was never
    submitted; it counts like one with no token, at its elapsed time.
    Returns the metrics and every due request's time to first token."""
    t1 = w["t_end"]
    due = w["live"]
    ttft = [(x.t_first if x.t_first is not None and x.t_first <= t1 else t1) - x.due
            for x in due] + [t1 - d for d in w["unsent"]]
    tpot = []
    for x in due:
        st = [(s, n) for s, n in x.stamps if s <= t1]
        if st and st[-1][1] >= 2:
            # The first stamp may carry more than one token only if a tick
            # emitted two, which the engine never does.
            tpot.append((st[-1][0] - st[0][0]) / (st[-1][1] - st[0][1]) * 1e3
                        if st[-1][1] > st[0][1] else 0.0)
    tokens = sum(max((n for s, n in x.stamps if s <= t1), default=0) for x in due)
    return {
        "ttft_p90_s": float(np.percentile(ttft, 90)) if ttft else None,
        "tpot_p90_ms": float(np.percentile(tpot, 90)) if tpot else None,
        "output_tokens_per_s": tokens / seconds,
    }, ttft


def run(ctx) -> Outcome:
    import jax

    sv = Served(ctx)
    engine = sv.engine()
    if "after_setup" in ctx.hooks:
        ctx.hooks["after_setup"](engine)
    c, s = sv.c, sv.s
    schedule = traffic.open_loop(ctx.mix, ctx.seed, ctx.seconds, c["vocab_size"])
    prof = harness.Profiler(ctx.cell["name"]) if ctx.trace else None
    trace_at = (0.3 * ctx.seconds, min(0.3 * ctx.seconds + 6.0, 0.9 * ctx.seconds))
    w = drive(engine, schedule, ctx.seconds, prof, trace_at)
    mem_peak = harness.memory_peak_bytes([sv.dev])
    e2e, ttft = window_metrics(w, ctx.seconds)
    due = w["live"]
    finished = [x for x in due if x.req.status == "done"]
    failed = sum(1 for x in due if x.req.status == "failed")
    late = [x.submitted - x.due for x in due]
    notes = [
        f"requests due in the window {len(ttft)}; finished by its close "
        f"{len(finished)}, failed {failed}, never submitted (a tick ran past "
        f"the close) {len(w['unsent'])}",
        f"waiting for a slot at the close: {w['queue'][-1][1] if w['queue'] else 0}",
        f"generator lateness p50 {np.percentile(late, 50) * 1e3:.1f} ms, "
        f"max {max(late) * 1e3:.1f} ms" if late else "generator: no request due",
        f"compilations inside the window: {w['compiles']}",
        "ttft of requests due, s: " + _table(ttft),
        "prompt tokens of requests submitted: " + _table([len(x.req.prompt) for x in due]),
        "output tokens of requests submitted: " + _table([x.req.max_new_tokens for x in due]),
        f"KV pages in use, peak over the window: {w['pages_peak']} of {engine.max_pages}",
    ]
    reduction, label = None, None
    rec = {
        "t_window": w["t_start"], "pages_peak": w["pages_peak"], "max_pages": engine.max_pages,
        "dims": roofline.Dims.from_config(c), "peaks": ctx.peaks,
    }
    if prof is not None:
        reduction, path = prof.reduce()
        notes.append(f"trace: {path}")
        B, C, d = s["max_batch"], s["prefill_chunk"], c["hidden_size"]
        pre = lambda e: any(f"[{B},{C},{d}]" in o.text for o in e.ops)  # noqa: E731
        dec = lambda e: not pre(e) and any(f"[{B},1,{d}]" in o.text for o in e.ops)  # noqa: E731
        label = lambda e: "prefill_step" if pre(e) else "decode_step" if dec(e) else e.name  # noqa: E731
        rec.update(reduction=reduction, ticks=w["ticks"],
                   prefill_execs=reduction.executions(pre),
                   decode_execs=reduction.executions(dec))
        notes += step_notes(reduction, label, rec, w["ticks"])

    # ---- correctness: the reference over a sample of finished requests -----
    sample = _sample(finished, ctx.seed)
    seqs = [(np.asarray(x.req.prompt), list(x.req.output)) for x in sample]
    W = sv.W
    del engine, sv, w, finished, sample, schedule, due
    gc.collect()  # the engine holds reference cycles; free its pool first
    jax.clear_caches()
    if "served" in ctx.hooks:  # a test puts other tokens in the program's place
        seqs = ctx.hooks["served"](W, c, seqs)
    gap, n_tok = served_gap(W, c, seqs)
    notes.append(f"reference: {len(seqs)} requests, {n_tok} served tokens compared")
    checks = ({"logit_gap_max": (gap, c["correctness"]["logit_gap_max"])} if seqs
              else {"finished_requests": (1.0, 0.0)})
    return Outcome(
        attempted=len(ttft), failed=failed, e2e=e2e, rec=rec, checks=checks,
        devices=[ctx.devices[0]], memory_peak_bytes=mem_peak, reduction=reduction,
        label=label, notes=notes,
    )


def step_notes(reduction, label, rec, ticks) -> list:
    """What the trace held, by the labels the readers use; a warning where
    the ticks did prefill or decode work that no traced program matched
    (the step programs are found by their activations' shapes)."""
    names = Counter(label(e) for e in reduction.executions())
    notes = [f"programs in the traced window (all chips): {dict(names)}"]
    did_prefill = any(t["prefill_rows"] for t in ticks)
    did_decode = any(t["decode_contexts"] for t in ticks)
    for kind, did in (("prefill", did_prefill), ("decode", did_decode)):
        if did and not rec[f"{kind}_execs"]:
            notes.append(f"bench: WARNING: the window ran {kind} work but no traced program "
                         f"matched the {kind} step's shape; its per-layer metrics read nothing")
    return notes


def _table(values) -> str:
    if not values:
        return "none"
    q = np.percentile(values, [0, 50, 90, 100])
    return (f"n {len(values)} min {q[0]:.6g} p50 {q[1]:.6g} p90 {q[2]:.6g} "
            f"max {q[3]:.6g} sum {sum(values):.6g}")


def _sample(finished, seed):
    """The longest finished request, then others drawn from the seed, until
    SAMPLE_TOKENS served tokens or SAMPLE_MAX_REQUESTS requests."""
    if not finished:
        return []
    longest = max(finished, key=lambda x: len(x.req.prompt) + len(x.req.output))
    rest = [x for x in finished if x is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], len(longest.req.output)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].req.output)
    return out


def served_gap(W, c, seqs, *, fp8: bool = False):
    """Widest gap between the reference's best logit and the logit of the
    token that was served (``fp8``: of the token the control puts first)."""
    import jax.numpy as jnp

    from bench.reference import qwen3

    worst, n_tok = 0.0, 0
    for prompt, out in seqs:
        P, n = len(prompt), len(out)
        toks = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        idx = np.arange(P - 1, P - 1 + n)
        ref = qwen3.logits_at(W, c, toks, idx)
        if fp8:
            pick = jnp.argmax(qwen3.logits_at(W, c, toks, idx, fp8=True), axis=-1)
        else:
            pick = np.zeros(ref.shape[0], np.int32)
            pick[:n] = out
        g = np.asarray(qwen3.gaps(ref, jnp.asarray(pick, jnp.int32)))[:n]
        worst = max(worst, float(g.max()))
        n_tok += n
    return worst, n_tok


def calibrate(ctx, seeds, control_seeds, seconds: float):
    """Readings of the compared number: the program on ``seeds``, the
    control (the float8 reference in the program's place) on
    ``control_seeds``, each over the requests a window of the cell's own
    load finished.  One process, so the programs compile once."""
    from types import SimpleNamespace

    rows = []
    for seed in sorted(set(seeds) | set(control_seeds)):
        sv = Served(SimpleNamespace(**dict(vars(ctx), seed=seed)))
        engine = sv.engine()
        sched = traffic.open_loop(ctx.mix, seed, seconds, sv.c["vocab_size"])
        w = drive(engine, sched, seconds)
        finished = [x for x in w["live"] if x.req.status == "done"]
        seqs = [(np.asarray(x.req.prompt), list(x.req.output)) for x in _sample(finished, seed)]
        del engine, w, finished
        gc.collect()
        row = {"seed": seed, "requests": len(seqs), "tokens": sum(len(o) for _, o in seqs)}
        if seed in seeds:
            row["program"] = {"logit_gap_max": served_gap(sv.W, sv.c, seqs)[0]}
        if seed in control_seeds:
            row["control"] = {"logit_gap_max": served_gap(sv.W, sv.c, seqs, fp8=True)[0]}
        rows.append(row)
        print(row, flush=True)
        del sv
        gc.collect()
    return rows
