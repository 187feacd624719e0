"""Plain float32 forward pass of a dense Qwen3 decoder (hf ``Qwen3ForCausalLM``).

Follows the published architecture: token embedding; per layer RMSNorm,
q/k/v projections, per-head RMSNorm of q and k, rotate-half RoPE with
``rope_theta``, causal grouped-query softmax attention with scale
1/sqrt(head_dim), output projection, residual; RMSNorm, SwiGLU MLP
(down(silu(gate(x)) * up(x))), residual; final RMSNorm; LM head tied to the
embedding.  No kernels, no cache, no batching: one sequence, every matmul in
float32 at ``Precision.HIGHEST``.

Weights come from ``bench/weights.py`` (bfloat16, the served type) and are
upcast one layer at a time, so a second float32 copy of the model is never
held.  ``fp8=True`` is the control: every linear layer's operands are
quantized to float8_e4m3 (weights per output channel, activations per row),
the step below bfloat16 that a serving change might take.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fq(x, axis):
    """Fake-quantize to float8_e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@partial(jax.jit, static_argnames=("hq", "hkv", "hd", "eps", "theta", "block", "fp8"))
def _layer(x, lw, *, hq, hkv, hd, eps, theta, block, fp8):
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = _rms(x, lw["ln1"], eps)
    q = _linear(h, lw["wq"], fp8).reshape(T, hq, hd)
    k = _linear(h, lw["wk"], fp8).reshape(T, hkv, hd)
    v = _linear(h, lw["wv"], fp8).reshape(T, hkv, hd)
    q = _rope(_rms(q, lw["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, lw["k_norm"], eps), pos, theta)
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)  # query head h reads kv head h // group
    v = jnp.repeat(v, group, axis=1)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = jax.lax.map(attend, jnp.arange(T // block)).reshape(T, hq * hd)
    x = x + _linear(o, lw["wo"], fp8)
    h = _rms(x, lw["ln2"], eps)
    y = jax.nn.silu(_linear(h, lw["gate"], fp8)) * _linear(h, lw["up"], fp8)
    return x + _linear(y, lw["down"], fp8)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, idx, final_norm, embed, *, eps, fp8):
    xs = _rms(x[idx], final_norm, eps)
    e = embed.astype(jnp.float32)
    if fp8:
        xs, e = _fq(xs, -1), _fq(e, -1)
    return jnp.einsum("nd,vd->nv", xs, e, precision=HI)


def logits_at(W, c: dict, tokens, idx, *, fp8: bool = False, bucket: int = 1024,
              block: int = 512):
    """float32 logits ``(len(idx), vocab)`` at positions ``idx`` of one sequence.

    The sequence is padded at its end to a multiple of ``bucket``; causal
    masking keeps the padding out of every real position.  ``idx`` is padded
    to a multiple of 64 (repeating its last entry), so that few shapes
    compile; the caller slices the first ``len(idx)`` rows.
    """
    tokens = np.asarray(tokens, np.int32)
    T = -(-len(tokens) // bucket) * bucket
    block = min(block, T)
    padded = np.zeros(T, np.int32)
    padded[: len(tokens)] = tokens
    x = jnp.take(W["embed"], jnp.asarray(padded), axis=0).astype(jnp.float32)
    kw = dict(hq=c["num_attention_heads"], hkv=c["num_key_value_heads"],
              hd=c["head_dim"], eps=float(c["rms_norm_eps"]),
              theta=float(c["rope_theta"]), block=block, fp8=fp8)
    for layer in range(c["num_hidden_layers"]):
        lw = jax.tree_util.tree_map(lambda a: a[layer], W["layers"])
        x = _layer(x, lw, **kw)
    idx = np.asarray(idx, np.int32)
    pad = np.full(-(-len(idx) // 64) * 64, idx[-1], np.int32)
    pad[: len(idx)] = idx
    return _head(x, jnp.asarray(pad), W["final_norm"], W["embed"], eps=kw["eps"], fp8=fp8)


@jax.jit
def gaps(ref, pick):
    """How far the reference logit of each picked token lies below the
    reference's best logit at that position."""
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
