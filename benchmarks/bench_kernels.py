"""Kernel micro-benchmarks: flash attention fwd + bwd, merge throughput,
and the backward tile-skip accounting.

(The Pallas path is validated in interpret mode by tests; wall-clock kernel
numbers on CPU are schedule checks, not TPU performance.  The *tile counts*
are exact, though — they evaluate the same position predicate the Pallas
kernels' ``pl.when`` skip does, so the zigzag-causal block-compute ratio
reported here is what the TPU kernels execute.)

``run(json_path=...)`` additionally writes the machine-readable
``BENCH_kernels.json`` consumed by the perf-trajectory tracking:
  * ``fwd`` / ``bwd``: wall time + achieved FLOP/s per config
    (bwd sweeps block sizes x causal/zigzag x GQA),
  * ``tile_skip``: computed/total backward tiles for zigzag-causal vs
    no-skip, window pruning, and the headline ``zigzag_over_noskip`` ratio
    (acceptance: <= ~0.6),
  * ``decode``: paged decode at 4k/32k contexts — fused kernel vs the
    dense-gather path, wall/token plus the exact peak-buffer column (the
    gather's materialized view vs the kernel's context-length-independent
    per-step blocks).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.merge import merge_partials
from repro.core.zigzag import to_zigzag, zigzag_positions
from repro.kernels.ops import backward_tile_counts, flash_attention

DEFAULT_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_kernels.json")


def _time(fn, *args, n=5):
    fn(*args)  # compile+warm
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _fwd_flops(B, Sq, Sk, H, D, frac):
    # two matmuls (scores, p@v) over the computed fraction of the matrix
    return 4.0 * B * H * Sq * Sk * D * frac


def _bwd_flops(B, Sq, Sk, H, D, frac):
    # five matmuls (recompute s, dp, dq, dk, dv) over the computed fraction
    return 10.0 * B * H * Sq * Sk * D * frac


def _bench_forward(rng):
    rows, recs = [], []
    for (B, S, H, D), causal in [((1, 2048, 8, 64), True), ((1, 4096, 8, 64), True)]:
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        fn = jax.jit(
            lambda q: flash_attention(q, q, q, causal=causal, impl="xla")[0]
        )
        dt = _time(fn, q)
        flops = _fwd_flops(B, S, S, H, D, 0.5 if causal else 1.0)
        print(f"| flash_xla B{B} S{S} H{H} D{D} causal={causal} | "
              f"{dt*1e3:.1f} ms | {flops/dt/1e9:.1f} GFLOP/s |")
        rows.append((f"flash_xla/S{S}", dt * 1e6, f"{flops/dt/1e9:.0f}GFLOPs"))
        recs.append(dict(
            name=f"flash_xla_fwd/S{S}", B=B, S=S, H=H, D=D, causal=causal,
            impl="xla", ms=dt * 1e3, gflops=flops / dt / 1e9,
        ))
    return rows, recs


def _bench_backward(rng):
    """Backward sweep: block sizes x causal/zigzag x GQA (impl=xla on CPU)."""
    rows, recs = [], []
    B, S, D, P = 1, 2048, 64, 4
    pos_zz = jnp.concatenate([zigzag_positions(S, P, j) for j in range(P)])
    for Hq, Hkv in [(8, 8), (8, 2)]:
        q32 = rng.standard_normal((B, S, Hq, D)).astype(np.float32)
        kv32 = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
        w32 = rng.standard_normal((B, S, Hq, D)).astype(np.float32)
        for layout, causal in [("contig", False), ("contig", True), ("zigzag", True)]:
            if layout == "zigzag":
                q = to_zigzag(jnp.asarray(q32, jnp.bfloat16), P, axis=1)
                kv = to_zigzag(jnp.asarray(kv32, jnp.bfloat16), P, axis=1)
                w = to_zigzag(jnp.asarray(w32, jnp.bfloat16), P, axis=1)
                pos = pos_zz
            else:
                q = jnp.asarray(q32, jnp.bfloat16)
                kv = jnp.asarray(kv32, jnp.bfloat16)
                w = jnp.asarray(w32, jnp.bfloat16)
                pos = jnp.arange(S, dtype=jnp.int32)
            for blk in [128, 256]:
                def loss(q, k, v):
                    out, _ = flash_attention(
                        q, k, v, q_pos=pos, k_pos=pos, causal=causal,
                        impl="xla", block_q=blk, block_k=blk,
                        block_q_bwd=blk, block_k_bwd=blk,
                    )
                    return jnp.sum(out * w)

                fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                dt = _time(fn, q, kv, kv, n=3)
                computed, total = backward_tile_counts(
                    pos[None], pos[None], block_q=blk, block_k=blk,
                    causal=causal,
                )
                frac = computed / total
                flops = _bwd_flops(B, S, S, Hq, D, frac)
                tag = (f"flash_bwd/{layout}{'_causal' if causal else ''}"
                       f"/Hq{Hq}Hkv{Hkv}/blk{blk}")
                print(f"| {tag} | {dt*1e3:.1f} ms | "
                      f"{flops/dt/1e9:.1f} GFLOP/s | tiles {computed}/{total} |")
                rows.append((tag, dt * 1e6, f"{flops/dt/1e9:.0f}GFLOPs"))
                recs.append(dict(
                    name=tag, B=B, S=S, Hq=Hq, Hkv=Hkv, D=D, causal=causal,
                    layout=layout, impl="xla", block_q_bwd=blk, block_k_bwd=blk,
                    ms=dt * 1e3, gflops=flops / dt / 1e9,
                    tiles_computed=computed, tiles_total=total,
                    tile_fraction=frac,
                ))
    return rows, recs


def _bench_decode(rng):
    """Paged decode: fused kernel vs the dense-gather path at 4k/32k.

    Wall/token on CPU compares an interpret-mode Pallas kernel against real
    XLA gathers — a schedule check, not TPU performance (the interpret rows
    use n=1).  The *peak-buffer* column is the structural point and is exact
    from the declared shapes: the gather path materializes the slot's full
    ``(B, W*page_size, Hkv, D)`` K and V views; the fused kernel's largest
    live buffer is one double-buffered page block + the ``(Hkv, group, D)``
    accumulators (``kernel_buffer_shapes("paged_decode")``), independent of
    context length.
    """
    from repro.analysis.kernel_lint import vmem_estimate
    from repro.kernels.ops import paged_decode_attention
    from repro.serving.kv_cache import PAD_POS

    rows, recs = [], []
    B, Hq, Hkv, D, ps, slack = 2, 8, 2, 64, 128, 8
    for S in (4096, 32768):
        used = -(-S // ps)
        W = used + slack
        n_pages = B * W + 1
        q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
        k_pool = jnp.asarray(
            rng.standard_normal((1, n_pages, ps, Hkv, D)), jnp.float32
        )
        pos = np.full((n_pages, ps), PAD_POS, np.int32)
        bt = np.full((B, W), n_pages, np.int32)
        pg = 0
        for b in range(B):
            for ip in range(used):
                bt[b, ip] = pg
                pos[pg] = np.arange(ip * ps, (ip + 1) * ps)
                pg += 1
        bt, pos = jnp.asarray(bt), jnp.asarray(pos)
        qp = jnp.full((B, 1), S - 1, jnp.int32)
        lens = jnp.full((B,), S, jnp.int32)
        itemsize = q.dtype.itemsize
        peak = {
            # K and V views, materialized every step, plus the int32 pos view
            "xla": B * W * ps * (2 * Hkv * D * itemsize + 4),
            # double-buffered per-grid-step blocks + scratch, page-count free
            "pallas_interpret": vmem_estimate(
                "paged_decode", block_q=Hq // Hkv, block_k=ps, D=D,
                data_bytes=itemsize, n_kv_heads=Hkv,
            ),
        }
        for impl, n in (("xla", 5), ("pallas_interpret", 1)):
            fn = jax.jit(
                lambda q, impl=impl: paged_decode_attention(
                    q, k_pool, k_pool, pos, bt, qp, 0, lengths=lens, impl=impl
                )[0]
            )
            dt = _time(fn, q, n=n)
            path = "gather" if impl == "xla" else "fused"
            tag = f"paged_decode/{path}/S{S}"
            print(f"| {tag} | {dt*1e3:.1f} ms/token | "
                  f"peak buffer {peak[impl]/2**20:.2f} MiB |")
            rows.append((tag, dt * 1e6, f"{peak[impl]/2**20:.2f}MiB"))
            recs.append(dict(
                name=tag, path=path, impl=impl, B=B, S=S, Hq=Hq, Hkv=Hkv,
                D=D, page_size=ps, pages_used=used, table_width=W,
                ms_per_token=dt * 1e3, peak_buffer_bytes=peak[impl],
            ))
    return rows, recs


def _tile_skip_record():
    """Exact backward block-compute counts (the acceptance numbers)."""
    S, P, blk = 8192, 4, 256
    pos_zz = jnp.concatenate([zigzag_positions(S, P, j) for j in range(P)])[None]
    pos_ct = jnp.arange(S, dtype=jnp.int32)[None]
    zz_c, total = backward_tile_counts(
        pos_zz, pos_zz, block_q=blk, block_k=blk, causal=True
    )
    noskip, _ = backward_tile_counts(
        pos_zz, pos_zz, block_q=blk, block_k=blk, causal=False
    )
    win, _ = backward_tile_counts(
        pos_ct, pos_ct, block_q=blk, block_k=blk, causal=True, window=1024
    )
    rec = {
        "S": S, "sp_degree": P, "block": blk,
        "zigzag_causal": {"computed": zz_c, "total": total},
        "no_skip": {"computed": noskip, "total": total},
        "window_1024_contig": {"computed": win, "total": total},
        # headline: zigzag-causal backward block-compute count vs no-skip
        "zigzag_over_noskip": zz_c / noskip,
    }
    print(f"| bwd tile skip S{S} P{P} blk{blk} | zigzag-causal "
          f"{zz_c}/{total} | no-skip {noskip}/{total} | "
          f"ratio {zz_c/noskip:.3f} | window(1024) {win}/{total} |")
    assert zz_c / noskip <= 0.6, (zz_c, noskip)
    return rec


def run(json_path=DEFAULT_JSON):
    rows = []
    rng = np.random.default_rng(0)

    fwd_rows, fwd_recs = _bench_forward(rng)
    rows += fwd_rows
    bwd_rows, bwd_recs = _bench_backward(rng)
    rows += bwd_rows
    dec_rows, dec_recs = _bench_decode(rng)
    rows += dec_rows
    tile_skip = _tile_skip_record()

    # merge throughput (the Update() of the paper)
    shape = (4, 2048, 8, 64)
    o1 = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    l1 = jnp.asarray(rng.standard_normal(shape[:-1]), jnp.float32)
    fn = jax.jit(lambda a, b, c, d: merge_partials(a, b, c, d)[0])
    dt = _time(fn, o1, l1, o1, l1)
    rows.append(("merge_partials/4x2048x8x64", dt * 1e6, ""))
    print(f"| merge_partials {shape} | {dt*1e3:.2f} ms |")

    if json_path:
        record = {
            "backend": jax.default_backend(),
            "fwd": fwd_recs,
            "bwd": bwd_recs,
            "decode": dec_recs,
            "tile_skip": tile_skip,
            "merge_partials_ms": dt * 1e3,
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=1)
        print(f"wrote {json_path}")
    return rows


if __name__ == "__main__":
    run()
