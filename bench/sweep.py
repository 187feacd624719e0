#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate at which the queue
of requests waiting for a slot does not grow across the window.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 0.2,0.3,...

One process: set-up once, then a fresh engine and one window per rate.  Prints one JSON line per rate; the knee is recorded in
PERF.md and the cell's rate (0.8 x knee) in its traffic file.  Not part of
a benchmark run.
"""

import argparse
import gc
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness, traffic
    from bench.drivers import serve

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    harness.enable_compile_cache()
    ctx = SimpleNamespace(config=harness.load_config(cell["config"]), seed=args.seed,
                          devices=jax.devices()[:1])
    mix = traffic.load_mix(cell["traffic"])
    sv = serve.Served(ctx)
    for rate in (float(r) for r in args.rates.split(",")):
        engine = sv.engine()
        sched = traffic.open_loop(mix, args.seed, args.seconds, sv.c["vocab_size"], rate=rate)
        w = serve.drive(engine, sched, args.seconds)
        e2e, ttft = serve.window_metrics(w, args.seconds)
        due = w["live"]
        q = np.array(w["queue"], dtype=float).reshape(-1, 2)
        quarter = lambda a, b: float(q[(q[:, 0] >= a * args.seconds) & (q[:, 0] < b * args.seconds), 1].mean())  # noqa: E731
        print(json.dumps({
            "rate": rate, "due": len(ttft), "unsent": len(w["unsent"]),
            "finished": sum(1 for x in due if x.req.status == "done"),
            "waiting_q2": quarter(0.25, 0.5), "waiting_q4": quarter(0.75, 1.0),
            "waiting_end": int(q[-1, 1]) if len(q) else 0,
            "pages_peak": w["pages_peak"], **e2e,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
        }), flush=True)
        del engine, w, due
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
