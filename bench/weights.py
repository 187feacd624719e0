"""Random weights of a dense Qwen3-style decoder, made from the seed.

One jitted call makes every weight on the device, in the type it is served
in.  The names here are the reference's own (``reference/qwen3.py``); a
driver maps them onto the program's parameter tree.  Scales: projections
N(0, 1/d_in); the embedding, tied to the LM head, N(0, 1/d_model), so that
logits have about unit variance at any width; every RMSNorm scale
1 + N(0, 0.1^2), so that a program that skipped one would show.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(c: dict) -> dict:
    d, L, F, V = (c["hidden_size"], c["num_hidden_layers"],
                  c["intermediate_size"], c["vocab_size"])
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return {
        "embed": (V, d),
        "final_norm": (d,),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "wq": (L, d, hq * hd), "wk": (L, d, hkv * hd), "wv": (L, d, hkv * hd),
            "wo": (L, hq * hd, d),
            "q_norm": (L, hd), "k_norm": (L, hd),
            "gate": (L, d, F), "up": (L, d, F), "down": (L, F, d),
        },
    }


def _make(key, c: dict, dtype):
    sh = shapes(c)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        sh, is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        if name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            w = jax.random.normal(k, shape, jnp.float32) * shape[-1] ** -0.5
        else:
            w = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
        out.append(w.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def make(seed: int, c: dict, dtype=jnp.bfloat16, device=None):
    """All weights from ``seed``, made on ``device`` (default: the first)."""
    device = device or jax.devices()[0]
    fn = jax.jit(
        lambda k: _make(k, c, dtype),
        out_shardings=jax.sharding.SingleDeviceSharding(device),
    )
    return fn(jax.random.PRNGKey(seed))
