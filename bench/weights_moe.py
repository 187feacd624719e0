"""Random weights of a Qwen3 mixture-of-experts decoder, made from the seed.

``bench/weights.py``'s scheme, one jitted call on the device, in the type the
model is served in: projections, the router and each expert N(0, 1/d_in);
the embedding and the untied LM head N(0, 1/d_model); every RMSNorm scale
1 + N(0, 0.1^2).  The layers are drawn one at a time (``lax.map``), so a
float32 draw of one layer's experts is the largest temporary.  The names are
the reference's own (``reference/qwen3_moe.py``); a driver maps them onto
the program's parameter tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def shapes(c: dict) -> dict:
    """Top-level shapes, and each layer's shapes without the layer axis."""
    d, V, E, F = (c["hidden_size"], c["vocab_size"], c["num_experts"],
                  c["moe_intermediate_size"])
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return {
        "embed": (V, d),
        "lm_head": (d, V),
        "final_norm": (d,),
        "layers": {
            "ln1": (d,), "ln2": (d,),
            "wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd), "wo": (hq * hd, d),
            "q_norm": (hd,), "k_norm": (hd,),
            "router": (d, E),
            "gate": (E, d, F), "up": (E, d, F), "down": (E, F, d),
        },
    }


def _draw(key, name, shape, dtype):
    w = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        w = 1.0 + 0.1 * w
    elif name == "embed":
        w = w * shape[-1] ** -0.5
    else:
        w = w * shape[-2] ** -0.5
    return w.astype(dtype)


def _make(key, c: dict, dtype):
    sh = shapes(c)
    k_top, k_layers = jax.random.split(key)
    top = [n for n in sh if n != "layers"]
    out = {n: _draw(k, n, sh[n], dtype)
           for k, n in zip(jax.random.split(k_top, len(top)), top)}
    names = sorted(sh["layers"])

    def layer(k):
        return {n: _draw(kn, n, sh["layers"][n], dtype)
                for kn, n in zip(jax.random.split(k, len(names)), names)}

    out["layers"] = jax.lax.map(layer, jax.random.split(k_layers, c["num_hidden_layers"]))
    return out


def make(seed: int, c: dict, dtype=jnp.bfloat16, device=None):
    """All weights from ``seed``, made on ``device`` (default: the first)."""
    device = device or jax.devices()[0]
    fn = jax.jit(lambda k: _make(k, c, dtype),
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(jax.random.PRNGKey(seed))
