"""Functional layer primitives (no flax — params are plain pytrees).

Conventions:
  * params are nested dicts of jnp arrays, stored in ``cfg.param_dtype``;
  * compute casts to ``cfg.dtype`` (bf16 on TPU) with fp32 accumulations in
    norms / softmax / losses;
  * init mirrors common practice: truncated-normal(0.02) embeddings, Lecun /
    scaled init for projections.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "constrain",
    "dense_init",
    "dense",
    "norm_init",
    "apply_norm",
    "embed_init",
    "apply_rope",
    "mlp_init",
    "mlp",
    "chunked_cross_entropy",
]


def _dtype(name: str):
    return jnp.dtype(name)


def constrain(x, pctx, spec_entries):
    """Sharding constraint helper (no-op without a mesh)."""
    if pctx is None or pctx.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pctx.mesh, P(*spec_entries))
    )


# ---------------------------------------------------------------------------
# dense / norm / embed
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, dtype="float32", scale=None):
    if scale is None:
        scale = 1.0 / np.sqrt(d_in)
    # Cast after scaling: a NumPy float64 scale would promote the leaf.
    p = {"w": (jax.random.normal(key, (d_in, d_out), _dtype(dtype)) * scale).astype(_dtype(dtype))}
    if bias:
        p["b"] = jnp.zeros((d_out,), _dtype(dtype))
    return p


def dense(p, x, compute_dtype):
    w = p["w"].astype(compute_dtype)
    y = jnp.einsum("...d,df->...f", x.astype(compute_dtype), w)
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


def norm_init(d: int, *, norm_type: str = "rmsnorm", dtype="float32"):
    if norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), _dtype(dtype))}
    if norm_type == "layernorm":
        return {"scale": jnp.ones((d,), _dtype(dtype)), "bias": jnp.zeros((d,), _dtype(dtype))}
    if norm_type == "nonparam_ln":  # olmo's non-parametric LayerNorm
        return {}
    raise ValueError(norm_type)


def apply_norm(p, x, *, norm_type: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if norm_type == "rmsnorm":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    elif norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    elif norm_type == "nonparam_ln":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(norm_type)
    return y.astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype="float32"):
    return {"table": jax.random.normal(key, (vocab, d), _dtype(dtype)) * 0.02}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def apply_rope(x, positions, theta: float):
    """Rotate-half RoPE.  ``x (B,S,H,D)``, ``positions (B,S)`` int32."""
    B, S, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)  # (half,)
    angles = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # (B,S,1,half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (swiglu / gelu)
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, f: int, *, mlp_type: str = "swiglu", dtype="float32"):
    ks = jax.random.split(key, 3)
    if mlp_type == "swiglu":
        return {
            "gate": dense_init(ks[0], d, f, dtype=dtype),
            "up": dense_init(ks[1], d, f, dtype=dtype),
            "down": dense_init(ks[2], f, d, dtype=dtype),
        }
    if mlp_type == "gelu":
        return {
            "in": dense_init(ks[0], d, f, bias=True, dtype=dtype),
            "out": dense_init(ks[1], f, d, bias=True, dtype=dtype),
        }
    raise ValueError(mlp_type)


def mlp(p, x, *, mlp_type: str = "swiglu", compute_dtype=jnp.bfloat16):
    if mlp_type == "swiglu":
        g = dense(p["gate"], x, compute_dtype)
        u = dense(p["up"], x, compute_dtype)
        return dense(p["down"], jax.nn.silu(g) * u, compute_dtype)
    if mlp_type == "gelu":
        h = jax.nn.gelu(dense(p["in"], x, compute_dtype))
        return dense(p["out"], h, compute_dtype)
    raise ValueError(mlp_type)


# ---------------------------------------------------------------------------
# chunked cross-entropy (never materializes full (B,S,V) logits)
# ---------------------------------------------------------------------------


def chunked_cross_entropy(x, w_lm, labels, *, mask=None, chunk: int = 1024,
                          compute_dtype=jnp.bfloat16, z_loss: float = 0.0,
                          pctx=None):
    """Mean CE of ``softmax(x @ w_lm)`` vs labels, computed in seq chunks.

    ``x (B,S,d)``, ``w_lm (d,V)``, ``labels (B,S)``, optional ``mask (B,S)``.
    Materializes only (B, chunk, V) logits at a time — the dominant activation
    spike of LM training otherwise (B*S*V floats).
    Returns (mean_loss, total_weight).
    """
    B, S, d = x.shape
    V = w_lm.shape[1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n = S // chunk
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)

    vocab_parallel = (
        pctx is not None and pctx.mesh is not None
        and V % max(pctx.sp_degree, 1) == 0
    )
    if vocab_parallel:
        # Vocab-parallel head: w_lm resident with V over the SP axes and d
        # REPLICATED.  With d sharded (the ZeRO storage layout) the chunk
        # einsum contracts over a sharded dim and XLA all-reduces full
        # (B, chunk, V) partials — measured 67 GB/device/step on
        # recurrentgemma's 256k vocab (§Perf iter 4).  This constraint is one
        # (d/dg, V/model)->(d, V/model) weight gather per step instead.
        from jax.sharding import NamedSharding, PartitionSpec as _P

        w_lm = jax.lax.with_sharding_constraint(
            w_lm, NamedSharding(pctx.mesh, _P(None, pctx.seq_spec()))
        )

    xc = jnp.moveaxis(x.reshape(B, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)

    def body(carry, blk):
        tot, wsum = carry
        xb, lb, mb = blk
        logits = jnp.einsum(
            "bsd,dv->bsv", xb.astype(compute_dtype), w_lm.astype(compute_dtype)
        ).astype(jnp.float32)
        if vocab_parallel:
            from jax.sharding import NamedSharding, PartitionSpec as _P

            logits = jax.lax.with_sharding_constraint(
                logits,
                NamedSharding(
                    pctx.mesh, _P(pctx.data_axis, None, pctx.seq_spec())
                ),
            )
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        ce = (lse - gold) * mb
        if z_loss:
            ce = ce + z_loss * (lse**2) * mb
        return (tot + ce.sum(), wsum + mb.sum()), None

    (tot, wsum), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), (xc, lc, mc))
    return tot / jnp.maximum(wsum, 1.0), wsum


def lm_cross_entropy(x, w_lm, labels, *, mask=None, chunk=1024,
                     compute_dtype=jnp.bfloat16, pctx=None):
    """LM-head cross entropy (the chunked path handles both single-device
    and distributed execution; sharding constraints inside do the rest)."""
    return chunked_cross_entropy(
        x, w_lm, labels, mask=mask, chunk=chunk, compute_dtype=compute_dtype,
        pctx=pctx,
    )
