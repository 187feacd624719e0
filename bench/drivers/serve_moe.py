"""Serving cells of a Qwen3 mixture-of-experts model: the open loop of
``serve.py`` (its ``drive``, ``window_metrics``, ``warm_up``) through
``ServingEngine`` with the program's dropless expert layer.

What differs from ``serve.py``: the configuration maps onto an
``ArchConfig`` with experts, the engine's prefill step has the
configuration's ``prefill_rows`` rows (fewer than its decode slots), the
weights come from ``weights_moe.py``, a
traced run also records the expert layer's device counters
(``engine.stats()["moe"]``) at the profiler's start and stop, and
correctness is decided by ``reference/qwen3_moe.py``, which runs every
expert on every token.  The compared number is the **mean**, over the
sampled served tokens (``serve.py``'s sample: at least 256 tokens or 8
requests), of the gap by which a served token's reference logit lies below
the reference's best logit at that position.  Not ``serve.py``'s widest
gap: a bfloat16 program and the float32 reference pick another top-k set
at a sixth of the (token, layer) pairs, where the 8th and 9th router
logits lie closer than bfloat16's rounding, and such a flip moves a
token's logits as far as the float8 control does.  The widest gap then
reads the flips alone, in the program as in the control; the mean weighs
how often the logits move, which follows the precision.
"""

from __future__ import annotations

import gc
from types import SimpleNamespace

import numpy as np

from bench import harness, roofline, roofline_moe, traffic, weights_moe
from bench.drivers.serve import _sample, _table, drive, step_notes, warm_up, window_metrics
from bench.harness import Outcome


def arch_config(c: dict):
    """The program's ``ArchConfig`` for a Qwen3-MoE configuration file."""
    from repro.models.config import ArchConfig

    if c["model_type"] != "qwen3_moe":
        raise ValueError(f"serve_moe runs qwen3_moe configurations, not {c['model_type']!r}")
    if not (c["norm_topk_prob"] and c["decoder_sparse_step"] == 1 and not c["mlp_only_layers"]):
        raise ValueError("serve_moe runs experts in every layer with renormalized top-k gates")
    return ArchConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["moe_intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["num_experts"], n_experts_per_token=c["num_experts_per_tok"],
        vocab_size=c["vocab_size"], qkv_bias=c["attention_bias"], qk_norm=True,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        param_dtype=c["torch_dtype"],
    )


def program_params(W: dict, abstract) -> dict:
    """The reference's weights under the program's parameter names; the
    shapes must match the program's own ``init``."""
    import jax

    L = W["layers"]
    tree = {
        "embed": {"table": W["embed"]},
        "lm_head": {"w": W["lm_head"]},
        "final_norm": {"scale": W["final_norm"]},
        "layers": {
            "attn": {
                "wq": {"w": L["wq"]}, "wk": {"w": L["wk"]}, "wv": {"w": L["wv"]},
                "wo": {"w": L["wo"]},
                "q_norm": {"scale": L["q_norm"]}, "k_norm": {"scale": L["k_norm"]},
            },
            "ln1": {"scale": L["ln1"]}, "ln2": {"scale": L["ln2"]},
            "moe": {"router": {"w": L["router"]}, "wg": L["gate"], "wu": L["up"],
                    "wd": L["down"]},
        },
    }
    want = jax.tree_util.tree_map(lambda a: tuple(a.shape), abstract)
    got = jax.tree_util.tree_map(lambda a: tuple(a.shape), tree)
    if want != got:
        raise ValueError(f"program parameter tree changed: {want} != {got}")
    return tree


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
        self.W = weights_moe.make(ctx.seed, self.c, dtype=jnp.dtype(self.c["torch_dtype"]),
                                  device=self.dev)
        self.params = program_params(
            self.W, jax.eval_shape(self.bundle.init, jax.random.PRNGKey(0)))

    def engine(self):
        from repro.serving.engine import ServingEngine

        s = self.s
        eng = ServingEngine(
            self.bundle, self.params, max_batch=s["max_batch"], max_len=s["max_len"],
            prefill_chunk=s["prefill_chunk"], prefill_rows=s["prefill_rows"],
            page_size=s["page_size"], max_pages=s["max_pages"], preempt=s["preempt"],
        )
        warm_up(eng, self.c["vocab_size"], s["page_size"], s["prefill_chunk"])
        return eng


class CountingProfiler(harness.Profiler):
    """The harness's profiler, also reading the expert layer's counters when
    the trace starts and after it stops (the loop has waited for the
    device at both)."""

    def __init__(self, cell, engine):
        super().__init__(cell)
        self.engine = engine
        self.counts = []

    def _read(self):
        self.counts.append(self.engine.stats().get("moe"))

    def start(self):
        self._read()
        super().start()

    def stop(self):
        super().stop()
        self._read()

    def deltas(self):
        """Counter increments over the traced window (they wrap at 2**32),
        or None where the program keeps no such counters."""
        if len(self.counts) != 2 or None in self.counts:
            return None
        a, b = self.counts
        return {k: (b[k] - a[k]) % 2**32 for k in a}


def run(ctx) -> Outcome:
    import jax

    sv = Served(ctx)
    engine = sv.engine()
    if "after_setup" in ctx.hooks:
        ctx.hooks["after_setup"](engine)
    c, s = sv.c, sv.s
    schedule = traffic.open_loop(ctx.mix, ctx.seed, ctx.seconds, c["vocab_size"])
    prof = CountingProfiler(ctx.cell["name"], engine) if ctx.trace else None
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
        "dims": roofline.Dims.from_config(c), "moe_dims": roofline_moe.MoeDims.from_config(c),
        "peaks": ctx.peaks,
    }
    if prof is not None:
        reduction, path = prof.reduce()
        notes.append(f"trace: {path}")
        B, R, C, d = s["max_batch"], s["prefill_rows"], s["prefill_chunk"], c["hidden_size"]
        pre = lambda e: any(f"[{R},{C},{d}]" in o.text for o in e.ops)  # noqa: E731
        dec = lambda e: not pre(e) and any(f"[{B},1,{d}]" in o.text for o in e.ops)  # noqa: E731
        label = lambda e: "prefill_step" if pre(e) else "decode_step" if dec(e) else e.name  # noqa: E731
        rec.update(reduction=reduction, ticks=w["ticks"],
                   prefill_execs=reduction.executions(pre),
                   decode_execs=reduction.executions(dec),
                   moe_counts=prof.deltas())
        notes += step_notes(reduction, label, rec, w["ticks"])
        notes.append(f"expert layer counters over the traced window: {rec['moe_counts']}")

    # ---- correctness: the reference over a sample of finished requests -----
    sample = _sample(finished, ctx.seed)
    seqs = [(np.asarray(x.req.prompt), list(x.req.output)) for x in sample]
    W = sv.W
    del engine, sv, w, finished, sample, schedule, due, prof
    gc.collect()  # the engine holds reference cycles; free its pool first
    jax.clear_caches()
    if "served" in ctx.hooks:  # a test puts other tokens in the program's place
        seqs = ctx.hooks["served"](W, c, seqs)
    g = served_gaps(W, c, seqs)
    notes.append(f"reference: {len(seqs)} requests, {g.size} served tokens compared; "
                 f"gap {_table(list(g))}, not the reference's best token at "
                 f"{int(np.count_nonzero(g))}")
    checks = ({"logit_gap_mean": (float(g.mean()), c["correctness"]["logit_gap_mean"])}
              if seqs else {"finished_requests": (1.0, 0.0)})
    return Outcome(
        attempted=len(ttft), failed=failed, e2e=e2e, rec=rec, checks=checks,
        devices=[ctx.devices[0]], memory_peak_bytes=mem_peak, reduction=reduction,
        label=label, notes=notes,
    )


def served_gaps(W, c, seqs, *, fp8: bool = False) -> np.ndarray:
    """Each served token's gap below the reference's best logit at its
    position, in the order of ``seqs`` (``fp8``: of the token the control
    puts first)."""
    import jax.numpy as jnp

    from bench.reference import qwen3, qwen3_moe

    out = [np.zeros(0, np.float32)]
    for prompt, served in seqs:
        P, n = len(prompt), len(served)
        toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        idx = np.arange(P - 1, P - 1 + n)
        ref = qwen3_moe.logits_at(W, c, toks, idx)
        if fp8:
            pick = jnp.argmax(qwen3_moe.logits_at(W, c, toks, idx, fp8=True), axis=-1)
        else:
            pick = np.zeros(ref.shape[0], np.int32)
            pick[:n] = served
        out.append(np.asarray(qwen3.gaps(ref, jnp.asarray(pick, jnp.int32)))[:n])
    return np.concatenate(out)


def _readings(g: np.ndarray) -> dict:
    return {"logit_gap_mean": float(g.mean()), "logit_gap_max": float(g.max())}


def calibrate(ctx, seeds, control_seeds, seconds: float):
    """Readings of the compared number, with the widest gap beside it: the
    program on ``seeds``, the control (the float8 reference in the
    program's place) on ``control_seeds``, each over ``serve.py``'s sample
    of the requests a window of the cell's own load finished; and, on the
    seeds of both, the routing flips of a bfloat16-rounded reference against
    the float32 one over the sampled sequences (the program computes in
    bfloat16)."""
    from bench.reference import qwen3_moe

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
            row["program"] = _readings(served_gaps(sv.W, sv.c, seqs))
        if seed in seeds and seed in control_seeds:
            pairs = flipped = 0
            for prompt, out in seqs:
                toks = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
                n, f = qwen3_moe.routing_flips(sv.W, sv.c, toks)
                pairs, flipped = pairs + n, flipped + f
            row["routing_flips"] = {"token_layers": pairs, "flipped": flipped}
        if seed in control_seeds:
            row["control"] = _readings(served_gaps(sv.W, sv.c, seqs, fp8=True))
        rows.append(row)
        print(row, flush=True)
        del sv
        gc.collect()
    return rows
