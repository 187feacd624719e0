"""Plain float32 causal attention, forward and backward, in blocks of queries.

``out = softmax(q k^T / sqrt(D), masked by position) v`` with grouped-query
heads (query head h reads kv head h // (Hq / Hkv)), and the gradients of
``sum(out * g)`` with respect to q, k and v.  Each block of queries is a
plain ``jnp`` function differentiated with ``jax.vjp``; the key and value
gradients of all blocks are summed.  Every matmul runs at
``Precision.HIGHEST``.  ``fp8=True`` is the control: q, k, v, g and the
probabilities enter their matmuls quantized to float8_e4m3 (one scale per
head), the step below bfloat16 that an attention change might take.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fq(x, axes):
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _attend(qb, k, v, qpos, kpos, *, causal, fp8):
    hq, hkv, hd = qb.shape[1], k.shape[1], qb.shape[2]
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    if fp8:
        qb, k, v = _fq(qb, (0, 2)), _fq(k, (0, 2)), _fq(v, (0, 2))
    s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(hd)
    if causal:
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if fp8:
        p = _fq(p, (1, 2))
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)


@partial(jax.jit, static_argnames=("causal", "fp8"))
def _block(qb, gb, qpos, k, v, kpos, *, causal, fp8):
    f = partial(_attend, qpos=qpos, kpos=kpos, causal=causal, fp8=fp8)
    out, vjp = jax.vjp(f, qb, k, v)
    if fp8:
        gb = _fq(gb, (0, 2))
    dq, dk, dv = vjp(gb)
    return out, dq, dk, dv


def attention_fwd_bwd(q, k, v, g, qpos, kpos, *, causal=True, block=512, fp8=False):
    """``q, g (S, Hq, D)``, ``k, v (S, Hkv, D)``, positions ``(S,)``; all on
    one device.  Returns float32 ``(out, dq, dk, dv)``."""
    f32 = jnp.float32
    q, k, v, g = (x.astype(f32) for x in (q, k, v, g))
    S = q.shape[0]
    block = min(block, S)
    outs, dqs = [], []
    dk = jnp.zeros_like(k)
    dv = jnp.zeros_like(v)
    for i in range(0, S, block):
        o, dq_b, dk_b, dv_b = _block(
            q[i:i + block], g[i:i + block], qpos[i:i + block], k, v, kpos,
            causal=causal, fp8=fp8,
        )
        outs.append(o)
        dqs.append(dq_b)
        dk, dv = dk + dk_b, dv + dv_b
    return jnp.concatenate(outs), jnp.concatenate(dqs), dk, dv
