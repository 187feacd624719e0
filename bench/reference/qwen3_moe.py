"""Plain float32 forward pass of a Qwen3 mixture-of-experts decoder (hf
``Qwen3MoeForCausalLM``).

Attention follows ``reference/qwen3.py`` (RMSNorm, q/k/v projections,
per-head RMSNorm of q and k, rotate-half RoPE, causal grouped-query softmax
attention, output projection, residual).  The feed-forward of every layer is
the published sparse block: router logits over all experts, the top-k of
them, softmax renormalized over those k (``norm_topk_prob``), and a SwiGLU
expert ``down(silu(gate(x)) * up(x))`` per expert.  Here **every** expert
runs on every token and is weighted by a gate that is zero outside the
token's top-k: no sort, no grouping, no capacity.  The LM head is untied.
One sequence, no cache, no batching, every matmul in float32 at
``Precision.HIGHEST``; the experts run in blocks of tokens so that a layer
fits on one chip.

Weights come from ``bench/weights_moe.py`` (bfloat16, the served type) and
are upcast one layer at a time.  ``prec`` rounds every linear layer's
operands before the float32 matmul: ``"fp8"`` is the control (float8_e4m3,
weights per output channel, activations per row, as ``qwen3.py``'s
``fp8=True``); ``"bf16"`` is the program's own operand precision, used only
to count routing flips against the float32 routing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen3 import HI, _fq, _rms, _rope


def _round(x, axis, prec):
    if prec == "fp8":
        return _fq(x, axis)
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _linear(x, w, prec):
    """``x @ w`` for a weight ``(..., d_in, d_out)``; the weight's scale runs
    over ``d_in`` (one per output channel)."""
    w = w.astype(jnp.float32)
    return jnp.matmul(_round(x, -1, prec), _round(w, -2, prec), precision=HI)


def _experts(h, lw, *, k, tblock, prec):
    """Every expert on every token, each weighted by its gate (zero outside
    the token's top-k).  Returns the output and the top-k expert ids."""
    T, d = h.shape
    logits = _linear(h, lw["router"], prec)  # (T, E)
    top_vals, top_ids = jax.lax.top_k(logits, k)
    picked = logits >= top_vals[:, -1:]
    gate = jax.nn.softmax(jnp.where(picked, logits, -jnp.inf), axis=-1)  # (T, E)
    wg, wu, wd = (_round(lw[n].astype(jnp.float32), -2, prec) for n in ("gate", "up", "down"))

    def block(i):
        hb = _round(jax.lax.dynamic_slice_in_dim(h, i * tblock, tblock), -1, prec)
        gb = jax.lax.dynamic_slice_in_dim(gate, i * tblock, tblock)
        a = jnp.einsum("td,edf->tef", hb, wg, precision=HI)
        b = jnp.einsum("td,edf->tef", hb, wu, precision=HI)
        m = _round(jax.nn.silu(a) * b, -1, prec)
        return jnp.einsum("tef,efd->td", m * gb[:, :, None], wd, precision=HI)

    y = jax.lax.map(block, jnp.arange(T // tblock)).reshape(T, d)
    return y, top_ids


@partial(jax.jit, static_argnames=("hq", "hkv", "hd", "k", "eps", "theta", "block", "tblock",
                                   "prec"))
def _layer(x, lw, *, hq, hkv, hd, k, eps, theta, block, tblock, prec):
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = _rms(x, lw["ln1"], eps)
    q = _linear(h, lw["wq"], prec).reshape(T, hq, hd)
    kk = _linear(h, lw["wk"], prec).reshape(T, hkv, hd)
    v = _linear(h, lw["wv"], prec).reshape(T, hkv, hd)
    q = _rope(_rms(q, lw["q_norm"], eps), pos, theta)
    kk = _rope(_rms(kk, lw["k_norm"], eps), pos, theta)
    group = hq // hkv
    kk = jnp.repeat(kk, group, axis=1)  # query head h reads kv head h // group
    v = jnp.repeat(v, group, axis=1)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HI) / np.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(attend, jnp.arange(T // block)).reshape(T, hq * hd)
    x = x + _linear(o, lw["wo"], prec)
    y, top_ids = _experts(_rms(x, lw["ln2"], eps), lw, k=k, tblock=tblock, prec=prec)
    return x + y, top_ids


@partial(jax.jit, static_argnames=("eps", "prec"))
def _head(x, idx, final_norm, lm_head, *, eps, prec):
    return _linear(_rms(x[idx], final_norm, eps), lm_head, prec)


def _forward(W, c, tokens, *, prec, bucket, block, tblock):
    """Final hidden states of the padded sequence and each layer's top-k."""
    tokens = np.asarray(tokens, np.int32)
    T = -(-len(tokens) // bucket) * bucket
    padded = np.zeros(T, np.int32)
    padded[: len(tokens)] = tokens
    x = jnp.take(W["embed"], jnp.asarray(padded), axis=0).astype(jnp.float32)
    kw = dict(hq=c["num_attention_heads"], hkv=c["num_key_value_heads"], hd=c["head_dim"],
              k=c["num_experts_per_tok"], eps=float(c["rms_norm_eps"]),
              theta=float(c["rope_theta"]), block=min(block, T), tblock=min(tblock, T),
              prec=prec)
    routes = []
    for layer in range(c["num_hidden_layers"]):
        lw = jax.tree_util.tree_map(lambda a: a[layer], W["layers"])
        x, top_ids = _layer(x, lw, **kw)
        routes.append(top_ids)
    return x, routes


def logits_at(W, c: dict, tokens, idx, *, fp8: bool = False, bucket: int = 1024,
              block: int = 512, tblock: int = 256):
    """float32 logits ``(len(idx) padded to 64, vocab)`` at positions ``idx``
    of one sequence (``fp8``: the control).  The sequence is padded at its
    end to a multiple of ``bucket``; causal masking keeps the padding out of
    every real position, and each token routes on its own.  The caller
    slices the first ``len(idx)`` rows."""
    prec = "fp8" if fp8 else "f32"
    x, _ = _forward(W, c, tokens, prec=prec, bucket=bucket, block=block, tblock=tblock)
    idx = np.asarray(idx, np.int32)
    pad = np.full(-(-len(idx) // 64) * 64, idx[-1], np.int32)
    pad[: len(idx)] = idx
    return _head(x, jnp.asarray(pad), W["final_norm"], W["lm_head"],
                 eps=float(c["rms_norm_eps"]), prec=prec)


def routing_flips(W, c: dict, tokens, *, bucket: int = 1024, block: int = 512,
                  tblock: int = 256) -> tuple:
    """(token, layer) pairs of one sequence, and how many of them pick
    another top-k set when every linear layer's operands are rounded to
    bfloat16 (the program's precision) than in float32."""
    n = len(tokens)
    kw = dict(bucket=bucket, block=block, tblock=tblock)
    _, ref = _forward(W, c, tokens, prec="f32", **kw)
    _, low = _forward(W, c, tokens, prec="bf16", **kw)
    flipped = 0
    for a, b in zip(ref, low):
        a, b = np.sort(np.asarray(a)[:n], -1), np.sort(np.asarray(b)[:n], -1)
        flipped += int(np.any(a != b, axis=-1).sum())
    return n * len(ref), flipped
