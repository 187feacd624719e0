"""Benchmark harness: one section per paper table/figure + roofline summary.

Prints ``name,us_per_call,derived`` CSV at the end (harness convention).
Any section that fails stops the run with a nonzero exit.

  * Table 1 analog  — per-scheme communication volumes (bench_comm_volume)
  * Figure 6 analog — per-step times, ring vs tokenring (bench_attention_steps;
    modeled on v5e constants + measured on 4 simulated devices in a child
    process, started before this process imports JAX)
  * serving — chunked-prefill TTFT / decode tok/s + per-schedule planner
    link bytes (bench_serving)
  * kernel micro-benchmarks (bench_kernels) — also writes the
    machine-readable ``benchmarks/BENCH_kernels.json`` (fwd+bwd wall time,
    achieved FLOP/s, backward tile-skip ratios) so the kernel perf
    trajectory is tracked across PRs
  * roofline summary — from the dry-run artifacts (roofline_report)
"""

from __future__ import annotations

import os
import subprocess
import sys


def _child(module: str, title: str, timeout: int) -> None:
    """Run a 4-simulated-device section in a child process; exit if it fails."""
    print("=" * 72)
    print(title, flush=True)
    proc = subprocess.run(
        [sys.executable, "-m", module], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH="src"), timeout=timeout,
    )
    print(proc.stdout[-2000:])
    if proc.returncode != 0:
        sys.exit(f"{module} failed (exit {proc.returncode}):\n{proc.stderr[-2000:]}")


def main() -> None:
    # The multi-device sections run first, in children: this process imports
    # no JAX until they have exited, so it never holds a device they need.
    _child(
        "benchmarks.bench_attention_steps",
        "Figure 6 analog: measured wall-clock (4 simulated devices)", 900,
    )
    # writes benchmarks/BENCH_overlap.json (sequential vs pipelined wall time
    # + modeled overlap + HLO dependency evidence)
    _child(
        "benchmarks.bench_overlap",
        "Overlap: sequential vs pipelined executor (4 simulated devices)", 3000,
    )

    from benchmarks import (
        bench_attention_steps,
        bench_comm_volume,
        bench_kernels,
        bench_serving,
        roofline_report,
    )

    rows = []
    print("=" * 72)
    print("Table 1 analog: communication volumes")
    rows += bench_comm_volume.run()

    print("=" * 72)
    print("Figure 6 analog: per-step attention times (modeled)")
    rows += bench_attention_steps.run()

    print("=" * 72)
    print("Serving: chunked prefill TTFT + planner link bytes per schedule")
    rows += bench_serving.run()

    print("=" * 72)
    print("Kernel micro-benchmarks (fwd + bwd + tile skip)")
    rows += bench_kernels.run(json_path=bench_kernels.DEFAULT_JSON)

    print("=" * 72)
    print("Roofline summary (from dry-run artifacts, where present)")
    roofline_report.main()

    print("=" * 72)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
