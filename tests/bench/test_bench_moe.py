"""The ``qwen3-30b-a3b-chat`` cell at a tiny size on the CPU, through
``bench/run.py`` and the ``serve_moe`` driver: a sound run reads ``correct``
true; each fault planted in the expert layer under the timed path, and the
float8 control in the program's place, reads ``correct`` false; and the
cell's five per-layer readers read a synthetic record.

The runs happen in a fresh interpreter (``python tests/bench/test_bench_moe.py``),
as ``bench_tiny.py`` does for the other cells: ``run.main`` sets the
process's compile cache."""

import copy
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen3-30b-a3b-chat"
FAULTS = ("top_k_less_one", "gates_not_renormalized", "lowest_gate_dropped")


def tiny_config():
    """Six layers of 8 experts, top-2, in bfloat16 as the cell: the sound
    program's own routing flips against the float32 reference are in the
    comparison, under the cell's limit."""
    c = json.loads((ROOT / "bench/configs/qwen3-30b-a3b-serve.json").read_text())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             num_hidden_layers=6, vocab_size=512, num_experts=8, num_experts_per_tok=2,
             moe_intermediate_size=32)
    c["serving"] = dict(impl="xla", max_batch=4, max_len=256, page_size=16, prefill_chunk=32,
                        prefill_rows=2, max_pages=48, preempt=True)
    return c


def tiny_mix(rate=100.0):
    m = json.loads((ROOT / "bench/traffic/chat-poisson-moe.json").read_text())
    m["arrivals"]["rate_per_s"] = rate
    m["prompt_tokens"].update(median=40, min=8, max=150)
    m["output_tokens"].update(median=12, min=6, max=24)
    return m


# ---- planted faults: a wrong expert layer in the program's place ------------


def _faulty_ffn(kind):
    """The expert layer with one fault, every expert computed densely."""
    import jax
    import jax.numpy as jnp

    def ffn(p, x, cfg, pctx, valid=None):
        B, S, d = x.shape
        K, dt = cfg.n_experts_per_token, jnp.dtype(cfg.dtype)
        xf = x.astype(jnp.float32)
        logits = xf @ p["router"]["w"].astype(jnp.float32)
        vals, ids = jax.lax.top_k(logits, K)
        gates = jax.nn.softmax(vals, axis=-1)
        if kind == "top_k_less_one":
            gates = jnp.concatenate(
                [jax.nn.softmax(vals[..., :-1], axis=-1), jnp.zeros_like(vals[..., -1:])], -1)
        elif kind == "gates_not_renormalized":
            gates = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), ids, axis=-1)
        elif kind == "lowest_gate_dropped":
            gates = gates.at[..., -1].set(0.0)
        w = (jax.nn.one_hot(ids, cfg.n_experts) * gates[..., None]).sum(-2)  # (B,S,E)
        g = jnp.einsum("bsd,edf->bsef", xf, p["wg"].astype(jnp.float32))
        u = jnp.einsum("bsd,edf->bsef", xf, p["wu"].astype(jnp.float32))
        y = jnp.einsum("bsef,efd,bse->bsd", jax.nn.silu(g) * u, p["wd"].astype(jnp.float32), w)
        if valid is not None:
            y = jnp.where(valid[..., None], y, 0.0)
        n = jnp.int32(B * S * K)
        return y.astype(dt), jnp.float32(0.0), {"routed": n, "touched": jnp.int32(cfg.n_experts)}

    return ffn


def _plant(kind, c):
    """An ``after_setup`` hook: the engine's two steps recompiled with the
    faulty expert layer, and warmed up again before the window."""
    import jax

    from bench.drivers.serve import warm_up
    from repro.models import transformer

    def hook(engine):
        transformer.moe_ffn = _faulty_ffn(kind)
        b = engine.bundle
        engine._step = jax.jit(b.decode_step_paged)
        engine._chunk_step = jax.jit(
            lambda params, tokens, state, rows: b.prefill_chunk_paged(params, tokens, state,
                                                                      *rows))
        s = c["serving"]
        warm_up(engine, c["vocab_size"], s["page_size"], s["prefill_chunk"], rounds=1)

    return hook


def _control(W, c, seqs):
    """The float8 reference served in the program's place: its own greedy
    tokens for each sampled prompt, as many as the program served."""
    import jax.numpy as jnp

    from bench.reference import qwen3_moe

    out = []
    for prompt, served in seqs:
        toks = [int(t) for t in prompt]
        for _ in served:
            lo = qwen3_moe.logits_at(W, c, toks, [len(toks) - 1], fp8=True)[0]
            toks.append(int(jnp.argmax(lo)))
        out.append((prompt, toks[len(prompt):]))
    return out


def main():
    sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(ROOT / "tests" / "bench")]
    import bench_tiny

    from repro.models import transformer

    seed = 2**31 + 29
    cfg, mix = tiny_config(), tiny_mix()
    sound = transformer.moe_ffn
    for trace in (0, 1):
        print(json.dumps({"scenario": f"sound_trace{trace}",
                          **bench_tiny._run(CELL, seed, trace, cfg, mix)}), flush=True)
    for kind in FAULTS:
        try:
            d = bench_tiny._run(CELL, seed, 0, copy.deepcopy(cfg), mix,
                                {"after_setup": _plant(kind, cfg)})
        finally:
            transformer.moe_ffn = sound
        print(json.dumps({"scenario": kind, **d}), flush=True)
    print(json.dumps({"scenario": "control",
                      **bench_tiny._run(CELL, seed, 0, cfg, mix, {"served": _control})}),
          flush=True)


# ---- the tests ---------------------------------------------------------------


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return {d["scenario"]: d for d in map(json.loads, r.stdout.splitlines())
            if d.get("scenario")}


def test_moe_sound_run(runs):
    for trace in (0, 1):
        d = runs[f"sound_trace{trace}"]
        assert d["rc"] == 0, d["stderr"][-20:]
        line = d["line"]
        assert line["correct"] is True, line["checks"]
        assert line["failed"] == 0 and line["attempted"] > 0
    m = runs["sound_trace0"]["line"]["metrics"]
    assert set(m) == {"setup_s", "ttft_p90_s", "tpot_p90_ms", "output_tokens_per_s"}
    counted = [x for x in runs["sound_trace1"]["stderr"] if "expert layer counters" in x]
    assert counted and "'routed'" in counted[0], runs["sound_trace1"]["stderr"][-20:]


@pytest.mark.parametrize("fault", FAULTS)
def test_moe_fault_is_not_correct(runs, fault):
    d = runs[fault]
    assert d["rc"] == 0 and d["line"]["correct"] is False, (fault, d["line"]["checks"])


def test_moe_control_in_the_programs_place_is_not_correct(runs):
    d = runs["control"]
    assert d["rc"] == 0 and d["line"]["correct"] is False, d["line"]["checks"]


# ---- the cell's per-layer readers on a synthetic record ----------------------


def _read(name, rec):
    from bench import harness

    return harness.read_metric(name, rec)


@pytest.fixture
def rec():
    """Two step executions on one chip (a prefill of 1000 ms and a decode
    of 100 ms), whose ops carry the expert layer's scopes, or the names the
    compiled v5e program gives its kernels: ``flash_fwd``, ``paged_decode``,
    and ``ragged-dot-none`` for a grouped matmul, which carries no scope."""
    sys.path.insert(0, str(ROOT))
    from bench import peaks, program_trace, roofline, roofline_moe, trace

    c = json.loads((ROOT / "bench/configs/qwen3-30b-a3b-serve.json").read_text())
    ms = 1_000_000
    call = 'custom-call(), custom_call_target="tpu_custom_call"'
    flash = f"%flash_fwd.4 = bf16[32,32,256,128] {call}"
    other = f"%custom-call.9 = bf16[8,128] {call}"
    ragged = f"%ragged-dot-none.2 = bf16[256,768] {call}"
    paged = f"%paged_decode.2 = bf16[32,4,8,128] {call}"
    ops = [trace.Op("route", 0, 50 * ms), trace.Op("gmm", 50 * ms, 400 * ms),
           trace.Op("combine", 450 * ms, 50 * ms), trace.Op(flash, 500 * ms, 450 * ms),
           trace.Op(other, 950 * ms, 50 * ms),
           trace.Op("gmm2", 1000 * ms, 60 * ms), trace.Op(ragged, 1060 * ms, 20 * ms),
           trace.Op(paged, 1080 * ms, 20 * ms)]
    pre = trace.Execution("jit_prefill_chunk_paged(1)", 0, 1000 * ms, ops[:5])
    dec = trace.Execution("jit_decode_step_paged(2)", 1000 * ms, 100 * ms, ops[5:])
    dev = trace.Device("/device:TPU:0", [pre, dec], ops)
    red = trace.Reduction(window_ns=2000 * ms, devices=[dev], host_spans=[])
    path = "jit(prefill_chunk_paged)/while/body/"
    scopes = {"route": path + "moe_route/top_k", "gmm": path + "moe_gmm/mul",
              "combine": path + "moe_combine/gather", flash: path + "flash_fwd/pallas_call",
              "gmm2": "jit(decode_step_paged)/while/body/moe_gmm/mul"}  # kernels: no path
    pt = program_trace.ProgramTrace(spans=[], scopes={"/device:TPU:0": scopes})
    return {
        "reduction": red, "prefill_execs": [pre], "decode_execs": [dec],
        "program_trace": pt, "peaks": peaks.PEAKS["TPU v5 lite"],
        "dims": roofline.Dims.from_config(c), "moe_dims": roofline_moe.MoeDims.from_config(c),
        "moe_counts": {"routed": 8 * 8 * 300, "touched": 8 * 120, "steps": 2},
        "ticks": [{"prefill_rows": [(0, 256), (256, 44)], "decode_contexts": []},
                  {"prefill_rows": [], "decode_contexts": [1000] * 32}],
    }


def test_moe_share_reads_the_expert_scopes(rec):
    # 50 + 400 + 50 + 60 ms under the scopes and the 20 ms ragged-dot
    # kernel, of 1100 ms of step time; flash, paged decode and the unnamed
    # kernel are not the expert layer's
    assert _read("moe_share.moe", rec) == pytest.approx(100 * 580 / 1100)


def test_moe_gmm_roofline_reads_the_counters(rec):
    d, f = 2048, 768
    routed, touched = 8 * 8 * 300, 8 * 120
    flops = 6 * d * f * routed
    nbytes = 3 * d * f * 2 * touched + 2 * d * 2 * routed
    least = max(flops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > flops / 197e12  # 960 experts' weights: memory-bound
    # the moe_gmm scope (400 + 60 ms) and the ragged-dot kernel (20 ms)
    assert _read("moe_gmm_roofline.moe", rec) == pytest.approx(100 * least / 0.480)


def test_moe_attention_rooflines_take_their_kernels_by_name(rec):
    """Only ``flash_fwd`` (450 ms) and ``paged_decode`` (20 ms): not the
    grouped-matmul or other kernels of the same steps."""
    from bench import roofline

    attn, peaks = rec["moe_dims"].attention, rec["peaks"]
    pre = roofline.prefill_attention(attn, [(0, 256), (256, 44)])
    dec = roofline.paged_decode(attn, [1000] * 32)
    assert (attn.n_heads, attn.n_kv_heads, attn.head_dim) == (32, 4, 128)
    assert _read("prefill_attn_roofline.moe", rec) == pytest.approx(
        100 * pre.min_seconds(peaks) / 0.450)
    assert _read("paged_decode_roofline.moe", rec) == pytest.approx(
        100 * dec.min_seconds(peaks) / 0.020)


def test_moe_mfu_counts_experts_not_a_dense_ffn(rec):
    from bench import roofline, roofline_moe

    dims = rec["moe_dims"]
    d, L, V = 2048, 8, 151936
    tokens = 300 + 32
    attn = roofline.serve_model_flops(dims.attention, [(0, 256), (256, 44)], [1000] * 32)
    per_token = L * (2 * d * 128 + 8 * 6 * d * 768)
    assert dims.token_expert_flops == per_token
    assert attn == (tokens * 2 * L * (2 * d * 4096 + 2 * d * 512)
                    + 32 * 2 * d * V
                    + 4 * 32 * 128 * L * (roofline._sum_contexts(0, 256)
                                          + roofline._sum_contexts(256, 44) + 32 * 1000))
    want = 100 * (attn + tokens * per_token) / (1.1 * 197e12)
    assert roofline_moe.serve_model_flops(dims, [(0, 256), (256, 44)], [1000] * 32) == (
        attn + tokens * per_token)
    assert _read("mfu.moe", rec) == pytest.approx(want)


def test_moe_readers_read_nothing_without_the_program_s_counters_and_scopes(rec):
    """The parent program: no counters, no expert scopes."""
    bare = dict(rec, moe_counts=None,
                program_trace=SimpleNamespace(spans=[], scopes={"/device:TPU:0": {"x": ""}},
                                              scope=lambda dev, op: ""))
    assert _read("moe_gmm_roofline.moe", bare) is None
    assert _read("moe_share.moe", bare) is None


if __name__ == "__main__":
    main()
