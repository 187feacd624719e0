#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (device start, weights from the seed,
compiling or loading every program from the cache, warming up the cell's own
shapes) runs first and is reported as ``setup_s``; then the window measures
for ``--seconds``.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` profiles part of the window in a run of its own and prints the
per-layer metrics.  After the window, what the timed path produced is
compared with the plain reference and ``correct`` says whether every
compared number is within its limit; the numbers and their limits are the
last lines on standard error and the ``checks`` of the result.  The last
line of standard output is the result, one JSON object.

Exits nonzero and prints no result without a TPU, with fewer chips than the
cell asks for, or outside a checkout of the program.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)  # bench/trace.py must not shadow the standard library


def _fail(msg: str, code: int = 1):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None, *, require_chip=True, bench=None, config=None, mix=None,
         hooks=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program under {ROOT / 'src'}: run from a checkout", 2)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere else

    from bench import harness
    from bench.peaks import peaks_for
    from bench.traffic import load_mix

    try:
        bench = bench or harness.load_benchmark()
        cell = harness.find_cell(bench, args.workload)
    except (OSError, harness.CellError) as e:
        return _fail(str(e), 2)

    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            return _fail(f"needs a TPU, found {len(devices)} {devices[0].platform} "
                         f"device(s) ({devices[0].device_kind})", 3)
        if len(devices) < cell["chips"]:
            return _fail(f"{args.workload} needs {cell['chips']} chips, "
                         f"found {len(devices)}", 3)
        try:
            peaks = peaks_for(devices[0].device_kind)
        except KeyError as e:
            return _fail(str(e), 3)
    else:
        peaks = peaks_for("TPU v5 lite")  # the arithmetic runs; no time is real
    harness.enable_compile_cache()

    config = config or harness.load_config(cell["config"])
    mix = mix or load_mix(cell["traffic"])
    driver = importlib.import_module(f"bench.drivers.{config['driver']}")
    ctx = SimpleNamespace(
        cell=cell, config=config, mix=mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices[: cell["chips"]], peaks=peaks,
        hooks=hooks or {},
    )
    out = driver.run(ctx)
    setup_s = out.rec["t_window"] - T0
    print(f"setup_s {setup_s:.3f}", file=sys.stderr)
    line = harness.result_line(bench, args.workload, out, ctx.trace, setup_s)
    for note in out.notes:
        print(note, file=sys.stderr)
    harness.print_checks(out)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
