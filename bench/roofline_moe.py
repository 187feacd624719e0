"""Operations and bytes of the mixture-of-experts serving cells, computed
from shapes and from the expert layer's own counters.

The grouped matmuls of a step run ``routed`` expert rows (8 a valid token
and layer) over ``touched`` experts (those with at least one row, summed
over layers).  Their least work:

    flops = 6 * d * f * routed                  (gate, up and down)
    bytes = 3 * d * f * 2 * touched             (each touched expert's weights, read once)
          + 2 * d * 2 * routed                  (each row's input and output)

A step's model FLOPs: attention and its projections as
``roofline.serve_model_flops`` counts them, the router over every expert
and the top-k experts for each valid token, and the LM head for each
decoded token.  Padded rows are not counted.

Which ops of a v5e trace are whose: the attention kernels by their names
(``flash_fwd``, ``paged_decode``), and the grouped matmuls by the name
scope ``moe_gmm`` or by the name XLA gives the Mosaic kernels that
``jax.lax.ragged_dot`` lowers to (``ragged-dot-none``,
``ragged-dot-metadata``), which carry no name scope.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from bench import roofline
from bench.program_trace import _INSTR
from bench.trace import is_kernel


def instruction(text: str) -> str:
    """An op's HLO instruction name ("" where its text has none)."""
    m = _INSTR.match(text)
    return m.group(1) if m else ""


def is_named_kernel(text: str, name: str) -> bool:
    """A Mosaic kernel whose instruction name holds ``name`` as a part
    (``flash_fwd.4``, ``jvp_flash_fwd_.1``)."""
    return is_kernel(text) and re.search(rf"(^|_){name}(_|\.|$)", instruction(text)) is not None


def is_gmm(path: str, text: str) -> bool:
    """An op of the grouped matmuls: under the ``moe_gmm`` scope, or one of
    the kernels ``ragged_dot`` lowers to."""
    return "moe_gmm" in path or instruction(text).startswith("ragged-dot")


@dataclass(frozen=True)
class MoeDims:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    n_experts: int
    top_k: int
    d_expert: int
    elem_bytes: int = 2  # bf16 weights and activations

    @classmethod
    def from_config(cls, c: dict) -> "MoeDims":
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], vocab=c["vocab_size"], n_experts=c["num_experts"],
            top_k=c["num_experts_per_tok"], d_expert=c["moe_intermediate_size"],
        )

    @property
    def attention(self) -> roofline.Dims:
        """The attention half of a layer, with no dense feed-forward."""
        return roofline.Dims(self.layers, self.d_model, self.n_heads, self.n_kv_heads,
                             self.head_dim, 0, self.vocab, self.elem_bytes)

    @property
    def token_expert_flops(self) -> float:
        """Router and top-k experts of one valid token, over all layers."""
        d, f = self.d_model, self.d_expert
        return self.layers * (2.0 * d * self.n_experts + self.top_k * 6.0 * d * f)


def expert_work(dims: MoeDims, routed: int, touched: int) -> roofline.Work:
    d, f, eb = dims.d_model, dims.d_expert, dims.elem_bytes
    return roofline.Work(6.0 * d * f * routed,
                         3.0 * d * f * eb * touched + 2.0 * d * eb * routed)


def serve_model_flops(dims: MoeDims, prefill_rows, decode_contexts) -> float:
    tokens = sum(n for _, n in prefill_rows) + len(decode_contexts)
    return (roofline.serve_model_flops(dims.attention, prefill_rows, decode_contexts)
            + tokens * dims.token_expert_flops)
