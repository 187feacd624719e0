"""What every cell shares: finding its files by name, the compile cache, the
window's clock, the profiler, per-layer metric readers and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json`` (whose ``driver`` key names the module in
``bench/drivers/`` that runs it), ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"  # fixed path: it is part of the compile cache's key
HOST_SPANS = ("engine_tick", "submit", "sp_step")


class CellError(Exception):
    """The cell cannot run here: exit nonzero and print no result."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports: end-to-end ones untraced, per-layer
    ones traced; a metric without ``workloads`` belongs to every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, rec: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(rec)``; None if it finds
    nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def enable_compile_cache() -> Path:
    import jax

    path = CACHE / "jax"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations while ``active``: the window should have
    none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT and self.active:
            self.count += 1
            self.seconds += duration

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


@contextmanager
def span(name: str):
    """A host span on the profiler's clock (free when no trace is taken)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class Profiler:
    """Profiles part of a window, in a run of its own; writes under CACHE."""

    def __init__(self, cell: str):
        self.dir = CACHE / "trace" / cell
        self.on = False
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))
        self.on = True
        self.t_start = time.perf_counter()

    def stop(self):
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False

    def reduce(self):
        from bench import trace

        path = trace.find_xplane(self.dir)
        return trace.reduce_file(path, HOST_SPANS), path


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


@dataclass
class Outcome:
    """What a driver hands back; the harness turns it into the result line."""

    attempted: int
    failed: int
    e2e: dict  # end-to-end metric name -> value (host clock)
    rec: dict  # what per-layer readers read
    checks: dict  # compared number -> (value, limit)
    devices: list  # the jax devices the cell used
    memory_peak_bytes: int
    reduction: object = None  # bench.trace.Reduction of a traced run
    label: object = None  # execution -> display name, for the breakdown
    notes: list = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v == v and v <= lim for v, lim in self.checks.values()
        )


def result_line(bench: dict, cell: str, out: Outcome, trace: bool, setup_s: float) -> dict:
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if trace:
        for m in metrics_for(bench, cell, True):
            v = read_metric(m["name"], out.rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
            else:
                out.notes.append(f"bench: WARNING: per-layer metric {m['name']} read nothing "
                                 "in this cell and is left out of the result line")
    else:
        values = dict(out.e2e, setup_s=setup_s)
        for m in metrics_for(bench, cell, False):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    d0 = out.devices[0]
    device = {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(out.devices), "memory_peak_bytes": out.memory_peak_bytes,
    }
    line = {
        "correct": out.correct, "attempted": int(out.attempted),
        "failed": int(out.failed), "metrics": metrics, "device": device,
    }
    if trace and out.reduction is not None:
        from bench import trace as tr

        device["busy_s"] = out.reduction.busy_s()
        device["window_s"] = out.reduction.window_s
        line["breakdown"] = tr.breakdown(out.reduction, out.label or (lambda e: e.name))
    line["checks"] = {
        k: {"value": float(v), "limit": float(lim)} for k, (v, lim) in out.checks.items()
    }
    return line


def print_checks(out: Outcome, stream=sys.stderr):
    for k, (v, lim) in out.checks.items():
        ok = v == v and v <= lim
        print(f"check {k}: {v:.6g} limit {lim:.6g} {'ok' if ok else 'FAIL'}",
              file=stream, flush=True)
