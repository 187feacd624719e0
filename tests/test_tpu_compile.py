"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib compiles
for a ``v5e:2x2`` topology that is described, not attached, and refuses what
the chip's compiler would refuse — block shapes that break Mosaic's (8, 128)
tiling rule, more VMEM than a kernel may use, programs that do not fit HBM.
Interpret mode (``tests/test_kernels.py``) checks results and cannot see any
of that.  Widths are Qwen3-1.7B's attention (Hq 16, Hkv 8, D 128, bf16).

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers import every test
file.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

HQ, HKV, D = 16, 8, 128
S = 2048
PAGE, PAGES_PER_SLOT, BATCH = 128, 32, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled program"
    return text


def _flash_args(sharding, S=S, B=1):
    bf16, i32 = jnp.bfloat16, jnp.int32
    return (
        _spec((B, S, HQ, D), bf16, sharding),
        _spec((B, S, HKV, D), bf16, sharding),
        _spec((B, S, HKV, D), bf16, sharding),
        _spec((B, S), i32, sharding),
    )


@pytest.mark.parametrize("B", [1, 2])
def test_flash_forward_compiles_for_v5e(one_chip, B):
    from repro.kernels.ops import flash_attention

    def fwd(q, k, v, pos):
        return flash_attention(
            q, k, v, q_pos=pos, k_pos=pos, causal=True, impl="pallas"
        )

    _compiled_text(fwd, *_flash_args(one_chip, B=B))


def test_flash_backward_compiles_for_v5e(one_chip):
    """``jax.grad`` through the flash custom_vjp runs both backward kernels
    (dq; dk/dv), with the ``+ dlse`` cotangent flowing since lse is used."""
    from repro.kernels.ops import flash_attention

    def loss(q, k, v, pos):
        out, lse = flash_attention(
            q, k, v, q_pos=pos, k_pos=pos, causal=True, impl="pallas"
        )
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    text = _compiled_text(
        jax.grad(loss, argnums=(0, 1, 2)), *_flash_args(one_chip, B=2)
    )
    assert text.count("tpu_custom_call") >= 3  # fwd + dq + dk/dv


@pytest.mark.parametrize("page", [16, PAGE])
def test_paged_decode_compiles_for_v5e(one_chip, page):
    from repro.kernels.ops import paged_decode_attention

    n_pages = BATCH * PAGES_PER_SLOT * PAGE // page
    W = PAGES_PER_SLOT * PAGE // page
    bf16, i32 = jnp.bfloat16, jnp.int32
    args = (
        _spec((BATCH, 1, HQ, D), bf16, one_chip),
        _spec((1, n_pages, page, HKV, D), bf16, one_chip),  # a one-layer pool
        _spec((1, n_pages, page, HKV, D), bf16, one_chip),
        _spec((n_pages, page), i32, one_chip),
        _spec((BATCH, W), i32, one_chip),
        _spec((BATCH, 1), i32, one_chip),
    )

    def decode(q, kp, vp, pp, bt, qp):
        return paged_decode_attention(q, kp, vp, pp, bt, qp, 0, impl="pallas")

    _compiled_text(decode, *args)


def test_tokenring_sp_attention_compiles_for_v5e_2x2(topo):
    """The paper's path on the four described chips: zigzag-causal TokenRing
    with the pipelined overlap executor, forward and backward, lowered
    through shard_map onto a ``("data", "model") = (1, 4)`` mesh.  The TPU
    program's permutes (async start/done pairs inside a loop with no
    ``known_trip_count``) carry exactly the modeled bytes per direction plus
    the traveling query halves' int32 positions."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.api import AttnShapes, ParallelContext, sp_attention
    from repro.core.compat import device_mesh
    from repro.launch.hlo_analysis import analyze_hlo

    n_dev, S_glob = 4, 4 * S
    mesh = device_mesh(np.array(topo.devices).reshape(1, n_dev), ("data", "model"))
    pctx = ParallelContext(
        mesh=mesh, sp_axes=("model",), data_axis="data", strategy="tokenring",
        layout="zigzag", impl="pallas", overlap=True,
    )
    seq = NamedSharding(mesh, P("data", "model"))
    args = _flash_args(seq, S=S_glob)

    def attn(q, k, v, pos):
        return sp_attention(q, k, v, pos, pos, pctx=pctx, causal=True)

    def loss(q, k, v, pos):
        return jnp.sum(attn(q, k, v, pos).astype(jnp.float32))

    stats = analyze_hlo(_compiled_text(attn, *args), world=n_dev)
    cost = pctx.plan(
        AttnShapes(B=1, Sq=S_glob, Hq=HQ, Hkv=HKV, D=D, dtype_bytes=2), causal=True
    ).cost
    pos_bytes = (n_dev - 1) * (S_glob // n_dev // 2) * 4
    assert (stats.link_bytes_fwd, stats.link_bytes_bwd) == (
        cost.fwd_bytes + pos_bytes, cost.bwd_bytes + pos_bytes,
    )
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert "collective-permute" in text


def _named_kernels_text(kernel, sharding):
    """Compiled v5e HLO of the program that runs ``kernel``."""
    from repro.kernels.ops import flash_attention, paged_decode_attention

    if kernel == "paged_decode":
        n_pages, W = BATCH * 4, 4
        bf16, i32 = jnp.bfloat16, jnp.int32
        args = (
            _spec((BATCH, 1, HQ, D), bf16, sharding),
            _spec((1, n_pages, PAGE, HKV, D), bf16, sharding),
            _spec((1, n_pages, PAGE, HKV, D), bf16, sharding),
            _spec((n_pages, PAGE), i32, sharding),
            _spec((BATCH, W), i32, sharding),
            _spec((BATCH, 1), i32, sharding),
        )
        return _compiled_text(
            lambda *a: paged_decode_attention(*a, 0, impl="pallas"), *args)

    def loss(q, k, v, pos):
        out, lse = flash_attention(q, k, v, q_pos=pos, k_pos=pos, causal=True, impl="pallas")
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    return _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *_flash_args(sharding, S=512))


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_decode"])
def test_kernels_named_in_compiled_v5e_hlo(one_chip, kernel):
    """Each Pallas kernel is a named instruction of the compiled program, the
    name a device profile shows for its op.  Transformations prefix it
    (``jvp_flash_fwd_``, ``transpose_jvp_flash_bwd_dq__``)."""
    import re

    text = _named_kernels_text(kernel, one_chip)
    names = [m.group(1) for m in re.finditer(r"^\s*(?:ROOT )?%([\w.\-]+) = .*tpu_custom_call",
                                             text, re.M)]
    assert any(re.search(rf"(^|_){kernel}(_|\.|$)", n) for n in names), (
        f"no custom call named {kernel} among {names}")


def _paged_step_compiled(cfg, sharding, step, *, B, R, pages, slot_pages, C=256):
    """A paged serving step compiled for the described v5e exactly as the
    engine jits it (``serving.engine.paged_steps``: the state donated).
    Returns the compiled program and the abstract serve state."""
    from repro.core.api import ParallelContext
    from repro.models import build_model
    from repro.serving.engine import paged_steps

    bundle = build_model(cfg, ParallelContext(mesh=None, impl="pallas"))
    place = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _spec(a.shape, a.dtype, sharding), t)
    params = place(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    state = place(jax.eval_shape(lambda: bundle.init_paged_state(pages, PAGE, B, slot_pages)))
    decode, prefill = paged_steps(bundle)
    i32 = jnp.int32
    if step == "prefill":
        fn, args = prefill, (params, _spec((R, C), i32, sharding), state,
                             _spec((3, R), i32, sharding))
    else:
        fn, args = decode, (params, _spec((B,), i32, sharding), state,
                            _spec((B,), jnp.bool_, sharding))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the compiled program"
    return compiled, state, jax.eval_shape(fn, *args)


def _assert_pool_updated_in_place(compiled, state, step, *, temp_below=None):
    """The donated pool is aliased to the step's result and no whole layer of
    it is copied (``hlo_analysis.pool_layer_ops``); the prefill step reads
    the layer it gathers its views from into VMEM, one read for K and one
    for V, since the same gather addressed into HBM made that step 40%
    slower on the chip.  Optionally the step's temporary buffers stay under
    ``temp_below`` bytes."""
    from repro.launch.hlo_analysis import pool_layer_ops

    pool = state["k"]
    pool_bytes = 2 * pool.size * pool.dtype.itemsize  # K and V
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, (mem.alias_size_in_bytes, pool_bytes)
    if temp_below is not None:
        assert mem.temp_size_in_bytes < temp_below, (mem.temp_size_in_bytes, temp_below)
    ops = pool_layer_ops(compiled.as_text(), pool.shape)
    assert not ops["moves"], ops["moves"]
    assert len(ops["vmem_layer_reads"]) == (2 if step == "prefill" else 0), ops


@pytest.mark.parametrize(
    "step,pages,B,slot_pages",
    [("decode", 64, 4, 8), ("prefill", 64, 4, 8),
     ("decode", 352, 16, 64), ("prefill", 352, 16, 64)],
    ids=["decode", "prefill", "decode_cell", "prefill_cell"])
def test_paged_steps_update_pool_in_place_for_v5e(one_chip, step, pages, B, slot_pages):
    """Two layers of Qwen3-1.7B's paged serving steps at published widths,
    pages of 128, jitted as the engine jits them: the pool rides in the
    layer scan's carry, the paged-decode kernel reads a layer by index, the
    prefill gather reads one layer into VMEM, and the state is donated, so
    the compiled step aliases the whole K/V pool and copies no layer of it.
    At 64 pages the decode and prefill temporaries stay under one layer's K;
    at the ``qwen3-longdoc`` cell's shapes (352 pages, batch 16, 64 pages a
    slot) the decode step's do, and the prefill step's views of 16 slots of
    8192 tokens outgrow a layer.  The VMEM read holds up to a layer of 448
    pages at these widths (117 MB); a pool past that fails here."""
    from repro.configs.qwen3_1_7b import CONFIG

    cfg = CONFIG.with_(n_layers=2, dtype="bfloat16", param_dtype="bfloat16")
    compiled, state, _ = _paged_step_compiled(
        cfg, one_chip, step, B=B, R=B // 2 if pages == 64 else B, pages=pages,
        slot_pages=slot_pages)
    k = state["k"]
    layer_k = k.size * k.dtype.itemsize // k.shape[0]
    _assert_pool_updated_in_place(
        compiled, state, step,
        temp_below=layer_k if step == "decode" or pages == 64 else None)


@pytest.mark.parametrize(
    "step", ["prefill", "decode", "prefill_8_rows",
             "decode_2_layers", "prefill_8_rows_2_layers"])
def test_moe_paged_steps_compile_for_v5e(one_chip, step):
    """One layer of Qwen3-30B-A3B's paged serving steps at published widths
    (32 q / 4 kv heads of 128, 128 experts of 768, top-8) at the serving
    cell's shapes (batch 32, chunk 256, 512 pages of 128, 36 a slot; prefill
    on 32 rows, and on the cell's 8), jitted as the engine jits them: the
    grouped matmuls, the sort and the flash or paged-decode kernel compile,
    and the step's expert counters come out of the program.  At two layers
    (a layer scan that stays a loop) the donated pool is updated in place,
    as in ``test_paged_steps_update_pool_in_place_for_v5e``."""
    from repro.configs.qwen3_moe_30b_a3b import CONFIG

    two = step.endswith("_2_layers")
    cfg = CONFIG.with_(n_layers=2 if two else 1, dtype="bfloat16", param_dtype="bfloat16")
    compiled, state, out = _paged_step_compiled(
        cfg, one_chip, step.split("_")[0], B=32,
        R=8 if step.startswith("prefill_8_rows") else 32, pages=512, slot_pages=36)
    assert set(state["moe"]) == {"routed", "touched", "steps"}
    assert set(out[1]["moe"]) == {"routed", "touched", "steps"}
    if two:
        # No temp bound here: the scan still copies each layer's expert
        # stacks out of the scanned weights, a temporary far above one
        # layer's K.
        _assert_pool_updated_in_place(compiled, state, step.split("_")[0])
