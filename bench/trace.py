"""Reduce a profiler trace to the numbers the per-layer metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  On a TPU each chip is a plane named
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per program
execution, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per
HLO instruction, named by its HLO text).  Asynchronous ops appear on a line
of their own and are not read: their span is a transfer in flight, and the
time the core waits for it is the ``*-done`` op on ``XLA Ops``.  Host spans
from ``jax.profiler.TraceAnnotation`` sit on the ``/host:CPU`` plane.
Times are in nanoseconds on the profiler's clock; device and host clocks
agree to about a millisecond.  The traced window runs from the start of the
first harness span to the end of the last: the profiler's own start and
stop, which the workload does not wait on, lie outside it.

A Mosaic (Pallas) kernel is an op whose HLO text has
``custom_call_target="tpu_custom_call"``.  Control-flow ops (a ``while`` of a
layer scan, a ``conditional``) span the ops of their bodies on the same
line; the reduction keeps only leaf ops, those that contain no other op.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

COLLECTIVE_OPS = (
    "collective-permute", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "send", "recv", "collective-broadcast",
)
_OPCODE = re.compile(r"^\s*%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")


def opcode(text: str) -> str:
    m = _OPCODE.match(text)
    return m.group(1) if m else ""


def is_kernel(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def is_collective(text: str) -> bool:
    op = opcode(text)
    return any(op == c or op.startswith(c + "-") for c in COLLECTIVE_OPS)


def operand_count(text: str) -> int:
    """Operands of an HLO instruction: the ``%name`` references in its call."""
    m = _OPCODE.match(text)
    if not m:
        return 0
    depth, args = 0, []
    for ch in text[m.end() - 1:]:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        args.append(ch)
    return "".join(args).count("%")


@dataclass
class Op:
    text: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Execution:
    """One execution of one program on one chip, with the ops inside it."""

    module: str
    start: int
    dur: int
    ops: list = field(default_factory=list)

    @property
    def end(self) -> int:
        return self.start + self.dur

    @property
    def name(self) -> str:
        return self.module.split("(")[0]

    def kernels(self) -> list:
        return [o for o in self.ops if is_kernel(o.text)]


@dataclass
class Device:
    name: str
    executions: list
    ops: list  # every op on the XLA Ops line, in time order

    def busy_ns(self) -> int:
        return union_length([(o.start, o.end) for o in self.ops])


@dataclass
class Reduction:
    window_ns: int
    devices: list
    host_spans: list  # (name, start, end)
    window_start: int = 0

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def busy_s(self) -> float:
        """Seconds of the window in which an op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns() for d in self.devices) / len(self.devices) / 1e9

    @property
    def window(self) -> tuple:
        return self.window_start, self.window_start + self.window_ns

    def executions(self, pred=None) -> list:
        out = [e for d in self.devices for e in d.executions]
        return [e for e in out if pred is None or pred(e)]


def union_length(intervals) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def subtract_length(a, b) -> int:
    """Length of the union of ``a`` minus the union of ``b``."""
    a, b = merge(a), merge(b)
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def exposed_collective_ns(ops) -> int:
    """Time in which a collective op runs and no other op does."""
    coll = [(o.start, o.end) for o in ops if is_collective(o.text)]
    rest = [(o.start, o.end) for o in ops if not is_collective(o.text)]
    return subtract_length(coll, rest)


def idle_gaps(devices, window, host_spans) -> list:
    """Gaps in which no op runs on a chip, each labelled by the host span
    that overlaps it most (``"no harness span"`` where none does)."""
    gaps = []
    for d in devices:
        busy = merge([(o.start, o.end) for o in d.ops])
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                best, label = 0, "no harness span"
                for name, hs, he in host_spans:
                    ov = min(e, he) - max(s, hs)
                    if ov > best:
                        best, label = ov, name
                gaps.append((label, s, e))
    return gaps


def breakdown(red: Reduction, label=lambda e: e.name, top: int = 10) -> dict:
    """The device ops that took most time, summed by program and opcode (a
    Mosaic kernel counts as ``kernel``), and the idle time of the chips summed
    by what the host was doing; seconds per chip."""
    per_op: dict = {}
    for d in red.devices:
        for e in d.executions:
            for o in e.ops:
                key = f"{label(e)}:{'kernel' if is_kernel(o.text) else opcode(o.text)}"
                per_op[key] = per_op.get(key, 0) + o.dur
    n = max(len(red.devices), 1)
    by_label: dict = {}
    for lbl, s, e in idle_gaps(red.devices, red.window, red.host_spans):
        by_label[lbl] = by_label.get(lbl, 0) + (e - s)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    top_idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v / n / 1e9] for k, v in top_idle],
    }


def find_xplane(trace_dir: str | Path) -> Path:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(files[-1])


def leaves(ops) -> list:
    """Ops that contain no other op (``ops`` sorted by start)."""
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and o.start <= nxt.start < o.end and nxt.end <= o.end:
            continue  # a container: the next op starts inside it
        out.append(o)
    return out


def _attach_ops(executions, ops):
    """Give each execution the ops that lie inside it (same chip)."""
    i = 0
    for e in executions:
        while i < len(ops) and ops[i].start < e.start:
            i += 1
        j = i
        while j < len(ops) and ops[j].start < e.end:
            e.ops.append(ops[j])
            j += 1
        i = j


def reduce_planes(planes, window_ns: int, span_names) -> Reduction:
    """``planes``: iterable of ``(name, {line_name: [(name, start, dur)]})``.
    The window is ``[0, window_ns)`` of the profile, narrowed to the harness
    spans (``span_names``) where there are any; ops and executions outside it
    are dropped and ops that straddle it are cut."""
    host = []
    for pname, lines in planes:
        if pname == "/host:CPU":
            for evs in lines.values():
                host.extend((n, s, s + d) for n, s, d in evs if n in span_names)
    host.sort(key=lambda h: h[1])
    w0, w1 = (host[0][1], max(h[2] for h in host)) if host else (0, window_ns)
    devices = []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            ops = leaves(sorted((Op(n, s, d) for n, s, d in lines.get("XLA Ops", [])),
                                key=lambda o: (o.start, -o.dur)))
            ops = [Op(o.text, max(o.start, w0), min(o.end, w1) - max(o.start, w0))
                   for o in ops if o.end > w0 and o.start < w1]
            ex = sorted((Execution(n, s, d) for n, s, d in lines.get("XLA Modules", [])
                         if s >= w0 and s + d <= w1), key=lambda e: e.start)
            _attach_ops(ex, ops)
            devices.append(Device(pname, ex, ops))
    devices.sort(key=lambda d: int(re.sub(r"\D", "", d.name) or 0))
    return Reduction(window_ns=w1 - w0, devices=devices, host_spans=host, window_start=w0)


def reduce_file(path: str | Path, span_names) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes, window_ns = [], None
    for p in pd.planes:
        if p.name == "Task Environment":
            st = dict(p.stats)
            window_ns = int(st["profile_stop_time"]) - int(st["profile_start_time"])
            continue
        lines = {}
        for ln in p.lines:
            lines[ln.name] = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in ln.events]
        planes.append((p.name, lines))
    if window_ns is None:
        raise ValueError(f"{path}: no profile start and stop time in the trace")
    return reduce_planes(planes, window_ns, span_names)
