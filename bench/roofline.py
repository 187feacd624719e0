"""Operations and bytes that each kernel and step needs, computed from shapes.

These are the algorithm's counts, not what a given implementation executes:
padded rows, masked tiles and recomputation beyond the algorithm's own are
not work.  A kernel's roofline share is

    max(flops / peak_flops, bytes / hbm_bw) / kernel_time

and a step's model-FLOP utilization is model flops / (device time x peak).
``Work`` objects add, so a reader sums the work of every call in a traced
window and divides once by the summed kernel time.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def min_seconds(self, peaks: dict) -> float:
        return max(self.flops / peaks["bf16_flops"], self.bytes / peaks["hbm_bw"])

    def bound(self, peaks: dict) -> str:
        return (
            "compute" if self.flops / peaks["bf16_flops"]
            >= self.bytes / peaks["hbm_bw"] else "memory"
        )


@dataclass(frozen=True)
class Dims:
    """The widths these counts need, read from a configuration file."""

    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    elem_bytes: int = 2  # bf16 activations, weights and K/V

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], d_ff=c["intermediate_size"],
            vocab=c["vocab_size"],
        )

    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o projections and the SwiGLU gate, up and down."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        return attn + 3 * d * self.d_ff


def _sum_contexts(start: int, n: int) -> int:
    """sum_{p=start}^{start+n-1} (p + 1): keys attended by n causal queries."""
    return n * start + n * (n + 1) // 2


# ---- serving: one model step -----------------------------------------------


def prefill_attention(dims: Dims, rows) -> Work:
    """Chunked-prefill attention over all layers of one step.

    ``rows``: ``(start, n)`` per request in the step: ``n`` prompt tokens at
    positions ``start .. start+n-1``, each attending causally to every earlier
    position.  Bytes: the K/V of positions in use, read once per layer, and
    the chunk's q and out.
    """
    L, hq, hkv, hd, eb = (dims.layers, dims.n_heads, dims.n_kv_heads,
                          dims.head_dim, dims.elem_bytes)
    flops = nbytes = 0.0
    for start, n in rows:
        flops += 4.0 * _sum_contexts(start, n) * hq * hd * L
        nbytes += L * ((start + n) * hkv * hd * eb * 2 + n * hq * hd * eb * 2)
    return Work(flops, nbytes)


def paged_decode(dims: Dims, contexts) -> Work:
    """Paged decode attention over all layers of one step.

    ``contexts``: per decoding request, the positions in use including the
    new token.  Bytes per layer: K/V of those positions, their int32
    positions, and q and out.
    """
    L, hq, hkv, hd, eb = (dims.layers, dims.n_heads, dims.n_kv_heads,
                          dims.head_dim, dims.elem_bytes)
    flops = nbytes = 0.0
    for c in contexts:
        flops += 4.0 * c * hq * hd * L
        nbytes += L * (c * hkv * hd * eb * 2 + c * 4 + hq * hd * eb * 2)
    return Work(flops, nbytes)


def serve_model_flops(dims: Dims, prefill_rows, decode_contexts) -> float:
    """Model FLOPs of the valid tokens of one step.

    Every valid token pays the layers' matmuls and its causal attention; a
    decoded token also pays the LM head.  Prefill rows produce no token, so
    their head is not counted; padded rows are not counted at all.
    """
    mm = 2.0 * dims.layers * dims.layer_matmul_params
    head = 2.0 * dims.d_model * dims.vocab
    att = 4.0 * dims.n_heads * dims.head_dim * dims.layers
    total = 0.0
    for start, n in prefill_rows:
        total += n * mm + att * _sum_contexts(start, n)
    for c in decode_contexts:
        total += mm + head + att * c
    return total


# ---- sequence-parallel attention: one forward + backward step ---------------


def causal_matmul_flops(S: int, hq: int, hd: int) -> float:
    """One S x S causal attention matmul: S^2/2 score entries x 2 x D x Hq."""
    return 2.0 * (S * S / 2) * hq * hd


def flash_fwd(S: int, hq: int, hkv: int, hd: int, eb: int = 2) -> Work:
    """Forward: QK^T and PV.  Bytes: q, k, v in; out and f32 lse out."""
    nbytes = S * (hq + 2 * hkv) * hd * eb + S * hq * hd * eb + S * hq * 4
    return Work(2 * causal_matmul_flops(S, hq, hd), nbytes)


def flash_bwd(S: int, hq: int, hkv: int, hd: int, eb: int = 2) -> Work:
    """Backward of the flash algorithm: the scores are recomputed from the
    lse (the algorithm stores no S x S matrix), then dP, dV, dQ, dK: five
    matmuls.  Bytes: q, k, v, out, dout, lse in; dq, dk, dv out."""
    nbytes = (
        S * (hq + 2 * hkv) * hd * eb  # q, k, v
        + 2 * S * hq * hd * eb  # out, dout
        + S * hq * 4  # lse
        + S * (hq + 2 * hkv) * hd * eb  # dq, dk, dv
    )
    return Work(5 * causal_matmul_flops(S, hq, hd), nbytes)


def attention_train_flops(S: int, hq: int, hd: int) -> float:
    """Model FLOPs of causal attention forward + backward: 2 matmuls forward,
    4 backward, no recomputation."""
    return 6 * causal_matmul_flops(S, hq, hd)
