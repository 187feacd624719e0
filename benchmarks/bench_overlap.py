"""Sequential vs pipelined executor wall time per SP strategy.

The double-buffered schedule executor (``core/schedule.py``) only moves
dependency edges — every transfer is issued against data in hand at step
entry.  This benchmark runs each ring strategy in both executor modes
(``ParallelContext(overlap=...)``) at S ∈ {2048, 8192} on simulated host
devices and records:

  * measured wall time per pass (best of ``repeats``), sequential vs
    pipelined, and the measured overlap fraction ``1 - pipe/seq``;
  * the planner's modeled times (v5e constants): ``sequential = compute +
    link``, ``pipelined = max(compute, link)``, and the modeled overlap
    fraction — the roofline-grade result;
  * the compiled-HLO dependency evidence (``overlap_report``): scan-body
    permutes blocked by same-step compute, pipelined vs sequential.

On the CPU harness collectives are memcpys with no async engine, so measured
wall times typically show parity — the dependency-graph columns are the
evidence that the pipelined program *can* overlap on hardware with async
collectives, which is exactly what the modeled columns quantify (see
docs/overlap.md).  Results land in ``benchmarks/BENCH_overlap.json``.

Run directly (sets device count before jax import):
  PYTHONPATH=src python -m benchmarks.bench_overlap [--smoke]
"""

import argparse
import json
import os
import time

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + os.environ.get("XLA_FLAGS", "")
    )

PEAK_FLOPS = 197e12  # v5e bf16 per chip
LINK_BW = 50e9  # bytes/s per ICI link direction

STRATEGIES = ["tokenring", "tokenring_faithful", "ring", "ring_bidir"]

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_overlap.json")


def bench(S_list, repeats=3, out_path=OUT_PATH):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ParallelContext, sp_attention
    from repro.core.api import AttnShapes
    from repro.core.compat import make_mesh
    from repro.core.zigzag import to_zigzag
    from repro.launch.hlo_analysis import overlap_report

    P_sp = 4
    mesh = make_mesh((1, P_sp), ("data", "model"))
    rng = np.random.default_rng(0)
    results = {}
    for strategy in STRATEGIES:
        results[strategy] = {}
        for S in S_list:
            q = jnp.asarray(rng.standard_normal((1, S, 8, 64)), jnp.float32)
            qz = to_zigzag(q, P_sp, axis=1)
            pos = to_zigzag(
                jnp.arange(S, dtype=jnp.int32)[None, :, None], P_sp, axis=1
            )[0, :, 0]

            row = {}
            for overlap in (True, False):
                pctx = ParallelContext(
                    mesh=mesh, data_axis=None, sp_axes=("model",),
                    strategy=strategy, impl="xla", block_q=256, block_k=256,
                    overlap=overlap,
                )
                fn = jax.jit(
                    lambda q, p, pctx=pctx: sp_attention(
                        q, q, q, p, p, pctx=pctx, causal=True
                    )
                )
                compiled = fn.lower(qz, pos).compile()  # AOT: one compile
                compiled(qz, pos).block_until_ready()  # warm up
                best = float("inf")
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    compiled(qz, pos).block_until_ready()
                    best = min(best, time.perf_counter() - t0)
                rep = overlap_report(compiled.as_text())
                mode = "pipelined" if overlap else "sequential"
                row[f"{mode}_wall_s"] = best
                row[f"{mode}_hlo_body_blocked"] = rep["scan_body_total"][
                    "compute_blocked"
                ]
                row[f"{mode}_hlo_body_permutes"] = rep["scan_body_total"][
                    "permutes"
                ]
                row[f"{mode}_hlo_blocked_total"] = rep["total"]["compute_blocked"]

            seq, pipe = row["sequential_wall_s"], row["pipelined_wall_s"]
            row["measured_overlap_fraction"] = 1.0 - pipe / seq if seq else 0.0

            plan = ParallelContext(
                mesh=mesh, data_axis=None, sp_axes=("model",),
                strategy=strategy, impl="xla",
            ).plan(
                AttnShapes(B=1, Sq=S, Hq=8, Hkv=8, D=64, dtype_bytes=4),
                causal=True,
            )
            row["modeled"] = plan.modeled_times(
                link_bw=LINK_BW, peak_flops=PEAK_FLOPS
            )
            results[strategy][str(S)] = row
            print(
                f"| {strategy:>20} S={S:>5} | seq {seq * 1e3:7.1f} ms | "
                f"pipe {pipe * 1e3:7.1f} ms | measured ovl "
                f"{row['measured_overlap_fraction'] * 100:5.1f}% | modeled ovl "
                f"{row['modeled']['overlap_fraction'] * 100:5.1f}% | "
                f"body blocked {row['pipelined_hlo_body_blocked']}"
                f"/{row['pipelined_hlo_body_permutes']} vs "
                f"{row['sequential_hlo_body_blocked']}"
                f"/{row['sequential_hlo_body_permutes']} |"
            )

            # At compute-dominated sizes pipelining should not lose (it wins
            # ~5-15% even on CPU); wall-clock is load-sensitive though (see
            # the verify skill's concurrent-jobs caveat), so a violation is
            # recorded + warned, never a mid-run abort that would discard
            # every row.  Small sizes are rendezvous-overhead noise — the
            # HLO columns are the result there.  The dependency-graph
            # assertions ARE deterministic and stay hard.
            row["wall_time_regression"] = bool(
                S // P_sp >= 512 and pipe > seq * 1.25
            )
            if row["wall_time_regression"]:
                print(
                    f"WARNING {strategy} S={S}: pipelined {pipe:.3f}s vs "
                    f"sequential {seq:.3f}s — rerun on an idle machine"
                )
            assert row["pipelined_hlo_body_blocked"] == 0, row
            if row["sequential_hlo_body_permutes"]:
                assert (
                    row["sequential_hlo_body_blocked"]
                    == row["sequential_hlo_body_permutes"]
                ), row

    payload = {
        "setup": {
            "devices": P_sp,
            "backend": jax.default_backend(),
            "shapes": {"B": 1, "Hq": 8, "D": 64, "S": list(S_list)},
            "peak_flops": PEAK_FLOPS,
            "link_bw": LINK_BW,
        },
        "results": results,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {out_path}")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes, no JSON rewrite (CI)")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    if args.smoke:
        bench([512], repeats=2, out_path=os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "BENCH_overlap_smoke.json"))
    else:
        bench([2048, 8192], repeats=args.repeats)


if __name__ == "__main__":
    main()
