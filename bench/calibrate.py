#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... --control-seeds 1,2,3 --seconds <s>

Runs the program on every seed of ``--seeds`` and the control (the plain
reference computed one precision step below the configuration's) on every
seed of ``--control-seeds``, in one process, and prints each reading and,
per compared number, the lower reading (the largest the program gives), the
upper reading (the smallest the control gives) and their ratio.  The limits
in the configuration file are set between the two.  Not part of a
benchmark run.
"""

import argparse
import importlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def summarize(rows) -> dict:
    names = sorted({k for r in rows for k in r.get("program", {})})
    out = {}
    for k in names:
        lo = max(r["program"][k] for r in rows if "program" in r)
        ups = [r["control"][k] for r in rows if "control" in r]
        up = min(ups) if ups else None
        out[k] = {"lower": lo, "upper": up, "ratio": (up / lo) if up and lo else None}
    return out


def main(argv=None, *, config=None, mix=None, devices=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, traffic

    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    harness.enable_compile_cache()
    config = config or harness.load_config(cell["config"])
    ctx = SimpleNamespace(
        cell=cell, config=config, mix=mix or traffic.load_mix(cell["traffic"]),
        devices=(devices or jax.devices())[: cell["chips"]], hooks={},
        seed=None, trace=False,
    )
    driver = importlib.import_module(f"bench.drivers.{config['driver']}")
    seeds = [int(x) for x in args.seeds.split(",")]
    control = [int(x) for x in args.control_seeds.split(",")]
    rows = driver.calibrate(ctx, seeds, control, args.seconds)
    print(json.dumps({"readings": rows, "summary": summarize(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
