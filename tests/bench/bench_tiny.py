"""Tiny cells for running the benchmark on the CPU: the cells' own drivers,
traffic kinds and references at sizes a test run holds."""

import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SECONDS = 1.5  # the tiny runs' window


def _load(rel):
    return json.loads((ROOT / rel).read_text())


def serve_config(**serving):
    """Six layers: with fewer, the float8 control's rounding adds up to a gap
    that reads near the cell's limit on some seeds."""
    c = _load("bench/configs/qwen3-1.7b-serve.json")
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=6, vocab_size=512)
    c["serving"] = dict(impl="xla", max_batch=4, max_len=256, page_size=16,
                        prefill_chunk=32, max_pages=48, preempt=True, **serving)
    return c


def serve_mix(rate=100.0):
    """Busy enough that every decode slot of the tiny engine is used."""
    m = _load("bench/traffic/longdoc-poisson.json")
    m["arrivals"]["rate_per_s"] = rate
    m["prompt_tokens"].update(median=60, min=20, max=180)
    m["output_tokens"].update(median=12, min=6, max=24)
    return m


def sp_config():
    c = _load("bench/configs/qwen3-1.7b-attn-sp4.json")
    c.update(num_attention_heads=4, num_key_value_heads=2, head_dim=32)
    c["attention"] = dict(c["attention"], impl="xla",
                          blocks={"block_q": 64, "block_k": 64})
    return c


def sp_mix(S=512):
    m = _load("bench/traffic/ring-32k-closed.json")
    m["sequence_tokens"] = S
    return m


def bench_with(cell_name, config_name, traffic_name, chips):
    b = copy.deepcopy(_load("BENCHMARK.json"))
    for w in b["workloads"]:
        if w["name"] == cell_name:
            w.update(config=config_name, traffic=traffic_name, chips=chips)
    return b


# ---- runs of the harness on the CPU, driven from a fresh interpreter -------
#
#   python tests/bench/bench_tiny.py serve|sp
#
# prints one JSON object per scenario: a sound run untraced and traced, each
# fault the cell can have planted under the timed path, the control put in
# the program's place, and the readings the control and the program give.
# The chip look is skipped; everything after it is the benchmark's own code.


def _run(workload, seed, trace, config, mix, hooks=None):
    import contextlib
    import io

    from bench import run

    buf, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(SECONDS),
                       "--trace", str(trace)], require_chip=False, config=config,
                      mix=mix, hooks=hooks)
    lines = buf.getvalue().strip().splitlines()
    return {"rc": rc, "line": json.loads(lines[-1]) if lines else None,
            "stderr": err.getvalue().splitlines()}


def _serve_faults():
    import time

    import jax.numpy as jnp

    from bench.reference import qwen3

    def token_altered(engine):
        step = engine._step
        engine._step = lambda *a: (lambda lo, st: (jnp.roll(lo, 1, axis=-1), st))(*step(*a))

    def state_unchanged(engine):
        chunk = engine._chunk_step
        engine._chunk_step = lambda params, tok, state, nv: (chunk(params, tok, state, nv)[0], state)

    def half_batch(engine):
        step = engine._step

        def half(*a):
            lo, st = step(*a)
            B = lo.shape[0]
            return lo.at[B // 2:].set(jnp.roll(lo[B // 2:], 7, axis=-1)), st

        engine._step = half

    def stall_at_close(engine):  # a tick 0.6 s into the window runs past its close
        run, first = engine.run, []

        def stalled(*a, **k):
            first.append(time.perf_counter())
            if len(first) > 1 and first[-1] - first[0] >= 0.6 and first[-2] - first[0] < 0.6:
                time.sleep(1.2)
            return run(*a, **k)

        engine.run = stalled

    def control(W, c, seqs):
        """The float8 reference served in the program's place: its own greedy
        tokens for each sampled prompt, as many as the program served."""
        out = []
        for prompt, served in seqs:
            toks = [int(t) for t in prompt]
            for _ in served:
                lo = qwen3.logits_at(W, c, toks, [len(toks) - 1], fp8=True)[0]
                toks.append(int(jnp.argmax(lo)))
            out.append((prompt, toks[len(prompt):]))
        return out

    return {"token_altered": {"after_setup": token_altered},
            "state_unchanged": {"after_setup": state_unchanged},
            "half_batch": {"after_setup": half_batch},
            "stall_at_close": {"after_setup": stall_at_close},
            "control": {"served": control}}


def _sp_faults():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    from bench.reference.attention import _attend, attention_fwd_bwd
    from repro.core.compat import shard_map

    def no_exchange(step, *, pctx, mesh, causal):
        spec, pspec = PS("data", "model", None, None), PS("data", "model")

        def local(q, k, v, p):  # every chip attends to its own keys only
            f = lambda q, k, v: _attend(q[0], k[0], v[0], p[0], p[0], causal=causal, fp8=False)
            return f(q, k, v)[None].astype(q.dtype)

        attn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec, pspec), out_specs=spec)

        def broken(q, k, v, pos, g):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, pos), q, k, v)
            return (out, *vjp(g.astype(out.dtype)))

        return jax.jit(broken)

    def token_altered(step, **_):
        return lambda *a: (lambda o, *gr: (o.at[0, 0].add(8.0), *gr))(*step(*a))

    def control(step, *, causal, **_):
        """The float8 reference in the program's place, on one chip."""
        dev = jax.devices()[0]

        def fp8_step(q, k, v, pos, g):
            ins = [jax.device_put(x[0], dev) for x in (q, k, v, g)]
            p = jax.device_put(pos[0], dev)
            res = attention_fwd_bwd(*ins, p, p, causal=causal, fp8=True)
            return tuple(r[None].astype(x.dtype) for r, x in zip(res, (q, q, k, v)))

        return fp8_step

    return {"no_exchange": {"step": no_exchange}, "token_altered": {"step": token_altered},
            "control": {"step": control}}


def main(kind):
    import os
    import sys

    if kind == "sp":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    for p in (str(ROOT / "src"), str(ROOT)):
        sys.path.insert(0, p)
    from types import SimpleNamespace

    import jax

    from bench import harness

    seed = 2**31 + 11  # run seeds may be wider than 32 signed bits
    if kind == "serve":
        wl, cfg, mix, faults = "qwen3-longdoc", serve_config(), serve_mix(), _serve_faults()
    else:
        wl, cfg, mix, faults = "tokenring-sp4-32k", sp_config(), sp_mix(), _sp_faults()
    for trace in (0, 1):
        print(json.dumps({"scenario": f"sound_trace{trace}",
                          **_run(wl, seed, trace, cfg, mix)}), flush=True)
    for name, hooks in faults.items():
        print(json.dumps({"scenario": name, **_run(wl, seed, 0, cfg, mix, hooks)}), flush=True)
    from bench.drivers import serve, sp_attention

    cell = harness.find_cell(harness.load_benchmark(), wl)
    ctx = SimpleNamespace(cell=cell, config=cfg, mix=mix, devices=jax.devices()[: cell["chips"]],
                          hooks={}, seed=None, trace=False)
    drv = serve if kind == "serve" else sp_attention
    rows = drv.calibrate(ctx, [seed, seed + 1], [seed, seed + 1], 1.5)
    print(json.dumps({"scenario": "control_readings", "rows": rows,
                      "limits": cfg["correctness"]}),
          flush=True)


if __name__ == "__main__":
    import sys

    main(sys.argv[1])
