"""The single-device expert layer (``models/moe.py``): dropless, exact against
an all-experts float32 oracle, blind to padding, independent of
``capacity_factor``; served end to end by ``ServingEngine`` within a stated
tolerance of the benchmark's plain reference; and the a2a expert-parallel
path still equal to it."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.api import ParallelContext
from repro.models.config import ArchConfig
from repro.models.moe import moe_ffn, moe_init

ROOT = Path(__file__).resolve().parents[1]
SINGLE = ParallelContext(mesh=None)


def _cfg(**kw):
    base = dict(name="m", family="moe", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                d_ff=48, vocab_size=64, n_experts=8, n_experts_per_token=3, moe_d_ff=48,
                dtype="float32", param_dtype="float32")
    return ArchConfig(**{**base, **kw})


def _oracle(p, x, cfg):
    """Every expert on every token, weighted by a gate that is zero outside
    the token's top-k (renormalized over those k); float32, highest
    precision."""
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("bsd,de->bse", x, p["router"]["w"], precision=hi)
    kth = jax.lax.top_k(logits, cfg.n_experts_per_token)[0][..., -1:]
    gate = jax.nn.softmax(jnp.where(logits >= kth, logits, -jnp.inf), axis=-1)
    g = jnp.einsum("bsd,edf->bsef", x, p["wg"], precision=hi)
    u = jnp.einsum("bsd,edf->bsef", x, p["wu"], precision=hi)
    y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, p["wd"], precision=hi)
    return jnp.einsum("bsed,bse->bsd", y, gate, precision=hi)


def _x(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("shape", [(2, 24, 32), (5, 1, 32)], ids=["prefill", "decode"])
def test_dropless_equals_all_experts_oracle(shape):
    """A prefill-shaped chunk (48 tokens, 144 entries over 8 experts, far
    beyond any capacity a dropping layer would give one expert) and a
    decode-shaped batch both equal the oracle."""
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(1), cfg)
    x = _x(shape)
    y, aux, counts = jax.jit(lambda p, x: moe_ffn(p, x, cfg, SINGLE))(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_oracle(p, x, cfg)),
                               atol=2e-5, rtol=2e-5)
    n = shape[0] * shape[1]
    assert int(counts["routed"]) == n * cfg.n_experts_per_token
    assert np.isfinite(float(aux))


def test_masked_tokens_add_zero_and_route_nowhere():
    cfg = _cfg()
    p = moe_init(jax.random.PRNGKey(2), cfg)
    x = _x((3, 16, 32), seed=3)
    valid = jnp.asarray(np.random.default_rng(4).random((3, 16)) < 0.6)
    y, _, counts = jax.jit(lambda p, x, v: moe_ffn(p, x, cfg, SINGLE, v))(p, x, valid)
    v = np.asarray(valid)
    assert not np.any(np.asarray(y)[~v])
    np.testing.assert_allclose(np.asarray(y)[v], np.asarray(_oracle(p, x, cfg))[v],
                               atol=2e-5, rtol=2e-5)
    # A masked token's value does not reach any valid token's output.
    x2 = jnp.where(valid[..., None], x, 1e3)
    y2, _, _ = moe_ffn(p, x2, cfg, SINGLE, valid)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))
    K = cfg.n_experts_per_token
    assert int(counts["routed"]) == int(v.sum()) * K
    logits = np.asarray(x)[v] @ np.asarray(p["router"]["w"])
    picked = np.argsort(-logits, axis=-1)[:, :K]
    assert int(counts["touched"]) == len(np.unique(picked))


@pytest.mark.parametrize("cf", [0.25, 1.0, 4.0])
def test_output_does_not_depend_on_capacity_factor(cf):
    """No capacity on one device: a factor that would drop most entries of
    a capacity dispatch changes nothing."""
    x = _x((2, 32, 32), seed=5)
    outs = []
    for factor in (1.25, cf):
        cfg = _cfg(capacity_factor=factor)
        y, _, counts = moe_ffn(moe_init(jax.random.PRNGKey(0), cfg), x, cfg, SINGLE)
        outs.append(np.asarray(y))
        assert int(counts["routed"]) == 64 * cfg.n_experts_per_token
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_bf16_param_dtype_gives_bf16_leaves(family):
    from repro.models.transformer import init_lm

    kw = dict(n_experts=8, n_experts_per_token=2, moe_d_ff=48) if family == "moe" else {}
    cfg = ArchConfig(name="b", family=family, n_layers=2, d_model=32, n_heads=2,
                     n_kv_heads=2, d_ff=48, vocab_size=64, qk_norm=True,
                     param_dtype="bfloat16", **kw)
    leaves = jax.tree_util.tree_leaves_with_path(init_lm(cfg, jax.random.PRNGKey(0)))
    wrong = [jax.tree_util.keystr(k) for k, a in leaves if a.dtype != jnp.bfloat16]
    assert not wrong, wrong


def test_engine_serves_qwen3_moe_within_tolerance_of_reference():
    """A tiny ``qwen3_moe`` configuration served by ``ServingEngine`` with
    the paged cache, packed chunked prefill and preemption: the logits of
    every decode step equal ``bench/reference/qwen3_moe.py``'s full forward
    over the prompt and the tokens served before it.  Both sides compute in
    float32 (the reference at HIGHEST precision), so they differ only in
    summation order: 1e-4 absolute on logits of about unit scale."""
    sys.path.insert(0, str(ROOT))
    from bench import weights_moe
    from bench.drivers.serve_moe import arch_config, program_params
    from bench.reference import qwen3_moe
    from repro.models import build_model
    from repro.serving.engine import ServingEngine

    c = {"name": "tiny-moe", "model_type": "qwen3_moe", "norm_topk_prob": True,
         "decoder_sparse_step": 1, "mlp_only_layers": [], "num_hidden_layers": 3,
         "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
         "num_experts_per_tok": 2, "vocab_size": 128, "attention_bias": False,
         "rope_theta": 1e6, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
         "torch_dtype": "float32"}
    bundle = build_model(arch_config(c), ParallelContext(mesh=None, impl="xla"))
    W = weights_moe.make(7, c, dtype=jnp.float32)
    params = program_params(W, jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    eng = ServingEngine(bundle, params, max_batch=3, max_len=96, prefill_chunk=8,
                        page_size=8, max_pages=14, preempt=True)
    seen = {}
    step = eng._step

    def recording(params, toks, state, mask):
        logits, state = step(params, toks, state, mask)
        lo = np.asarray(jax.block_until_ready(logits))
        for i in np.flatnonzero(np.asarray(mask)):
            r = eng.slots[i]
            seen[(r.uid, len(r.output))] = lo[i]
        return logits, state

    eng._step = recording
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, 128, n, dtype=np.int32), max_new_tokens=m)
            for n, m in ((29, 20), (17, 24), (33, 12))]
    eng.run()
    assert all(r.status == "done" for r in reqs)
    assert eng.counters["preemptions"] > 0  # the pool of 14 pages cannot hold all three
    assert eng.stats()["moe"]["routed"] > 0
    for r in reqs:
        P, n = len(r.prompt), len(r.output)
        toks = np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])
        ref = np.asarray(qwen3_moe.logits_at(W, c, toks, np.arange(P - 1, P - 1 + n),
                                             bucket=64, block=64, tblock=64))[:n]
        got = np.stack([seen[(r.uid, j)] for j in range(n)])
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=0)
        assert list(np.argmax(ref, -1)) == r.output  # greedy, as served


def test_a2a_expert_parallel_equals_single_device_layer():
    """``strategy_check moe``: the shard_map all-to-all dispatch on a (2, 4)
    mesh, at a capacity that drops nothing, equals the single-device
    dropless layer, forward and gradients."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-m", "repro.testing.strategy_check", "moe"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ALL CHECKS PASSED" in r.stdout
