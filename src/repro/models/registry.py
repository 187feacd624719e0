"""Model registry: one uniform bundle per architecture family.

``build_model(cfg, pctx)`` returns a :class:`ModelBundle` exposing:
  * ``init(key) -> params``
  * ``loss(params, batch) -> (loss, metrics)``         (train / prefill fwd)
  * ``decode_step(params, token_ids, state)``          (serving)
  * ``init_serve_state(batch, max_len) -> state``
  * ``input_specs(shape) -> (kind, batch-spec dict)``   (ShapeDtypeStructs)

The spec functions are what the multi-pod dry-run lowers against — no real
allocation ever happens for the full-size configs.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.api import ParallelContext
from repro.models.config import ArchConfig, ShapeConfig

__all__ = ["ModelBundle", "build_model", "input_specs"]


@dataclass
class ModelBundle:
    cfg: ArchConfig
    pctx: ParallelContext
    init: Callable[[Any], Any]
    loss: Callable[[Any, Any], Any]
    decode_step: Callable[[Any, Any, Any], Any] | None
    init_serve_state: Callable[..., Any] | None
    prefill: Callable[..., Any] | None = None
    # Chunked serving prefill: (params, tokens (B,C), state, n_valid (B,))
    # -> (logits, state').  Families without it fall back to token-by-token
    # cache filling in the serving engine — only sound when
    # ``decode_rollback_safe`` is set.
    prefill_chunk: Callable[..., Any] | None = None
    # Paged serving (serving/kv_cache.py page pool): same contracts as
    # ``decode_step`` / ``prefill_chunk`` but against the paged state built by
    # ``init_paged_state(n_pages, page_size, max_batch, slot_pages)``.
    # Families without them serve through the dense slab only.
    decode_step_paged: Callable[..., Any] | None = None
    prefill_chunk_paged: Callable[..., Any] | None = None
    init_paged_state: Callable[..., Any] | None = None
    # Whether the serve state is cache-style (per-slot ``len``/``pos``
    # bookkeeping, position-masked):  the engine's token-by-token fallback
    # prefill feeds dummy tokens to other rows and rolls back only ``len``,
    # which is sound for caches (the garbage slot is overwritten before it is
    # ever attended) but corrupts recurrent hidden state (ssm / RG-LRU rows
    # advance irreversibly).  Recurrent families need masked decode steps
    # before they can serve batched.
    decode_rollback_safe: bool = False
    encode: Callable[..., Any] | None = None  # enc-dec: fill cross KV

    def input_specs(self, shape: ShapeConfig):
        return input_specs(self.cfg, shape)

    def serve_state_specs(self, shape: ShapeConfig):
        """Shape-only serve state via eval_shape (no allocation)."""
        B = shape.global_batch
        max_len = shape.seq_len
        return jax.eval_shape(lambda: self.init_serve_state(B, max_len))


# ---------------------------------------------------------------------------
# per-family bundles
# ---------------------------------------------------------------------------


def build_model(cfg: ArchConfig, pctx: ParallelContext) -> ModelBundle:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        from repro.models import transformer as T

        # The serving steps are named functions, not lambdas: a jitted step
        # compiles to the module ``jit_<name>`` (``jit_decode_step_paged``,
        # ``jit_prefill_chunk_paged``), which is how a profile tells them apart.
        def decode_step(params, tok, state, active=None):
            return T.lm_decode_step(params, tok, state, active, cfg=cfg, pctx=pctx)

        def prefill_chunk(params, tok, state, n_valid):
            return T.lm_prefill_chunk(params, tok, state, n_valid, cfg=cfg, pctx=pctx)

        def decode_step_paged(params, tok, state, active=None):
            return T.lm_decode_step_paged(params, tok, state, active, cfg=cfg, pctx=pctx)

        def prefill_chunk_paged(params, tok, state, n_valid, row_slot=None, row_start=None):
            return T.lm_prefill_chunk_paged(
                params, tok, state, n_valid, row_slot, row_start, cfg=cfg, pctx=pctx
            )

        return ModelBundle(
            cfg=cfg,
            pctx=pctx,
            init=partial(_init_wrap, T.init_lm, cfg),
            loss=lambda params, batch: T.lm_loss(params, batch, cfg=cfg, pctx=pctx),
            decode_step=decode_step,
            init_serve_state=lambda B, max_len: T.init_decode_cache(
                cfg, B, max_len, pctx
            ),
            prefill=lambda params, tokens, positions, cache, prefix_embeds=None: T.lm_prefill(
                params, tokens, positions, cache, prefix_embeds, cfg=cfg, pctx=pctx
            ),
            prefill_chunk=prefill_chunk,
            decode_step_paged=decode_step_paged,
            prefill_chunk_paged=prefill_chunk_paged,
            init_paged_state=lambda n_pages, page_size, max_batch, slot_pages: T.init_paged_decode_cache(
                cfg, n_pages=n_pages, page_size=page_size,
                max_batch=max_batch, slot_pages=slot_pages, pctx=pctx
            ),
            decode_rollback_safe=True,
        )
    if fam == "ssm":
        from repro.models import mamba as M

        return ModelBundle(
            cfg=cfg,
            pctx=pctx,
            init=partial(_init_wrap, M.init_mamba_lm, cfg),
            loss=lambda params, batch: M.mamba_loss(params, batch, cfg=cfg, pctx=pctx),
            decode_step=lambda params, tok, state: M.mamba_decode_step(
                params, tok, state, cfg=cfg, pctx=pctx
            ),
            init_serve_state=lambda B, max_len: M.init_mamba_state(cfg, B),
        )
    if fam == "hybrid":
        from repro.models import rglru as R

        return ModelBundle(
            cfg=cfg,
            pctx=pctx,
            init=partial(_init_wrap, R.init_rg, cfg),
            loss=lambda params, batch: R.rg_loss(params, batch, cfg=cfg, pctx=pctx),
            decode_step=lambda params, tok, state: R.rg_decode_step(
                params, tok, state, cfg=cfg, pctx=pctx
            ),
            init_serve_state=lambda B, max_len: R.init_rg_state(cfg, B),
        )
    if fam == "encdec":
        from repro.models import encdec as E

        return ModelBundle(
            cfg=cfg,
            pctx=pctx,
            init=lambda key: E.init_encdec(cfg, key, max_dec_len=32768),
            loss=lambda params, batch: E.encdec_loss(params, batch, cfg=cfg, pctx=pctx),
            decode_step=lambda params, tok, state: E.encdec_decode_step(
                params, tok, state, cfg=cfg, pctx=pctx
            ),
            init_serve_state=lambda B, max_len: E.init_encdec_state(
                cfg, B, max_len, cfg.enc_seq
            ),
            decode_rollback_safe=True,  # cache-style state (len/pos)
            encode=lambda params, frames, state: E.encdec_encode(
                params, frames, state, cfg=cfg, pctx=pctx
            ),
        )
    raise ValueError(f"unknown family {fam!r}")


def _init_wrap(fn, cfg, key):
    return fn(cfg, key)


# ---------------------------------------------------------------------------
# input specs per (arch x shape) cell
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ArchConfig, shape: ShapeConfig):
    """Returns ``(kind, specs)``: the step to lower and its batch ShapeDtypeStructs.

    kind: "train" (loss+grad), "prefill" (fwd + cache fill), "decode" (1 token).
    """
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind

    if kind in ("train", "prefill"):
        if cfg.family == "encdec":
            specs = {
                "frames": _sds((B, cfg.enc_seq, cfg.d_model), cfg.dtype),
                "tokens": _sds((B, S), jnp.int32),
                "labels": _sds((B, S), jnp.int32),
                "positions": _sds((B, S), jnp.int32),
            }
        elif cfg.family == "vlm":
            S_text = S - cfg.frontend_tokens
            specs = {
                "tokens": _sds((B, S_text), jnp.int32),
                "labels": _sds((B, S_text), jnp.int32),
                "positions": _sds((B, S), jnp.int32),
                "patch_embeds": _sds((B, cfg.frontend_tokens, cfg.d_model), cfg.dtype),
            }
        else:
            specs = {
                "tokens": _sds((B, S), jnp.int32),
                "labels": _sds((B, S), jnp.int32),
                "positions": _sds((B, S), jnp.int32),
            }
        return kind, specs

    if kind == "decode":
        return kind, {"token_ids": _sds((B,), jnp.int32)}

    raise ValueError(kind)


def runnable(cfg: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether this (arch x shape) cell runs; reason if skipped.

    long_500k requires sub-quadratic attention (DESIGN.md skip list).
    """
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "pure full-attention arch: 500k-context decode skipped"
    return True, ""
