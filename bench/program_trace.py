"""What a traced run's profile holds beyond the reduction the kept readers
use (``bench/trace.py``): the program's own host spans, named ``engine.*``
and carrying their metadata as event stats, and the name-scope path of each
device op (``ring_send``, ``ring_compute``, ``ring_merge`` in a ring step).

``load(rec)`` returns them for the run whose reduction is ``rec["reduction"]``.
A driver may hand them over as ``rec["program_trace"]``; otherwise the profile
is found again under the profiler's directory (``.bench_cache/trace/<cell>/``)
as the newest ``.xplane.pb`` whose harness spans give the reduction's window,
read once and kept in ``rec``.  A profile of a program without the spans or
scopes gives empty ones, and the readers that need them read nothing.

On a TPU the scope path is the ``tf_op`` stat of an op's event *metadata*
(``jit(sp_step)/jvp()/shard_map/ring_merge/add:``), which
``jax.profiler.ProfileData`` does not show; ``read_scopes`` reads it from the
profile's protobuf (``XSpace``) with a schema of just the fields it needs.

Loading also prints, to standard error, the step programs counted by name
beside the reduction's count by shape, and the flash kernels by name beside
the count by operand count, with a ``bench: WARNING`` where they differ.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

from bench import trace
from bench.harness import CACHE, HOST_SPANS

PROGRAM_PREFIX = "engine."
SERVE_PROGRAMS = {"prefill": "jit_prefill_chunk_paged", "decode": "jit_decode_step_paged"}
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")


@dataclass
class Span:
    name: str
    start: int
    end: int
    meta: dict = field(default_factory=dict)


@dataclass
class ProgramTrace:
    spans: list  # Span, sorted by start
    scopes: dict = field(default_factory=dict)  # device plane -> {op's HLO text: scope path}

    def scope(self, device: str, op) -> str:
        return self.scopes.get(device, {}).get(op.text, "")


def kernel_name(text: str) -> str | None:
    """Which named flash kernel an op is: its instruction name holds the
    kernel's name as a part (``jvp_flash_fwd_.1``,
    ``transpose_jvp_flash_bwd_dq__.3``)."""
    m = _INSTR.match(text)
    if not m:
        return None
    for k in FLASH_KERNELS:
        if re.search(rf"(^|_){k}(_|\.|$)", m.group(1)):
            return k
    return None


def from_planes(planes) -> ProgramTrace:
    """The program spans of ``planes``: an iterable of
    ``(name, {line: [(name, start, dur, stats)]})``."""
    spans = [Span(n, s, s + d, dict(st)) for pname, lines in planes if pname == "/host:CPU"
             for evs in lines.values() for n, s, d, st in evs if n.startswith(PROGRAM_PREFIX)]
    spans.sort(key=lambda sp: sp.start)
    return ProgramTrace(spans)


def _read_planes(path):
    """The host plane of a profile file, as ``from_planes`` takes it."""
    from jax.profiler import ProfileData

    out = []
    for p in ProfileData.from_file(str(path)).planes:
        if p.name == "/host:CPU":
            out.append((p.name, {
                ln.name: [(e.name, int(e.start_ns), int(e.duration_ns),
                           dict(e.stats) if e.name.startswith(PROGRAM_PREFIX) else {})
                          for e in ln.events] for ln in p.lines}))
    return out


def _xspace_class():
    """The profile's ``XSpace`` message, cut to the fields ``read_scopes``
    needs (the field numbers are the profiler's ``xplane.proto``'s); the
    rest is skipped when parsing."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto", package="bench_xspace")

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, ftype, rep, tname in fields:
            m.field.add(name=fname, number=num, type=ftype,
                        label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL,
                        type_name=f".bench_xspace.{tname}" if tname else None)

    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, 0, None),
        ("str_value", 5, F.TYPE_BYTES, 0, None), ("ref_value", 7, F.TYPE_UINT64, 0, None))
    msg("XEventMetadata", ("name", 2, F.TYPE_BYTES, 0, None),
        ("stats", 5, F.TYPE_MESSAGE, 1, "XStat"))
    msg("XStatMetadata", ("id", 1, F.TYPE_INT64, 0, None), ("name", 2, F.TYPE_BYTES, 0, None))
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, "XStatMetadata"))
    msg("XPlane", ("name", 2, F.TYPE_BYTES, 0, None),
        ("event_metadata", 4, F.TYPE_MESSAGE, 1, "EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, 1, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xspace.XSpace"))


def read_scopes(data: bytes) -> dict:
    """``{device plane: {op's HLO text: scope path}}`` from a profile's bytes:
    the ``tf_op`` stat of each TPU op's event metadata, without its
    ``:<type>`` tail."""
    space = _xspace_class()()
    space.ParseFromString(data)
    out = {}
    for plane in space.planes:
        pname = plane.name.decode()
        if not pname.startswith("/device:TPU:"):
            continue
        names = {e.key: e.value.name.decode() for e in plane.stat_metadata}
        tf_op = [k for k, v in names.items() if v == "tf_op"]
        lut = {}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if tf_op and st.metadata_id == tf_op[0]:
                    path = st.str_value.decode() if st.str_value else names.get(st.ref_value, "")
                    text = entry.value.name.decode("utf-8", "replace")
                    lut[text] = path.rpartition(":")[0] or path
        out[pname] = lut
    return out


def _window(planes) -> tuple | None:
    host = [(s, s + d) for pname, lines in planes if pname == "/host:CPU"
            for evs in lines.values() for n, s, d, _ in evs if n in HOST_SPANS]
    if not host:
        return None
    return min(h[0] for h in host), max(h[1] for h in host)


def find_profile(window: tuple, *, scopes: bool) -> ProgramTrace | None:
    """The program trace of the newest profile the harness's profiler wrote
    whose harness spans give ``window`` (``scopes``: with its ops' scope
    paths)."""
    files = glob.glob(str(CACHE / "trace" / "**" / "*.xplane.pb"), recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        planes = _read_planes(path)
        if _window(planes) == tuple(window):
            pt = from_planes(planes)
            if scopes:
                pt.scopes = read_scopes(Path(path).read_bytes())
            return pt
    return None


def load(rec: dict, *, scopes: bool = False) -> ProgramTrace | None:
    """The program trace of the run ``rec`` records (``scopes``: with the
    device ops' scope paths), or None where the run was not traced."""
    pt = rec.get("program_trace")
    if pt is not None and (not scopes or pt.scopes):
        return pt
    red = rec.get("reduction")
    if red is None or not red.devices:
        return None
    pt = find_profile(red.window, scopes=scopes)
    if pt is not None:
        rec["program_trace"] = pt
        for note in name_notes(rec, pt):
            print(note, file=sys.stderr, flush=True)
    return pt


def name_notes(rec: dict, pt: ProgramTrace) -> list:
    """Programs and kernels counted by their names beside the counts the
    kept readers use (activation shapes; operand counts)."""
    red, notes = rec["reduction"], []
    if "prefill_execs" in rec:
        by_name = {k: len(red.executions(lambda e, n=n: e.name == n))
                   for k, n in SERVE_PROGRAMS.items()}
        by_shape = {k: len(rec.get(f"{k}_execs") or []) for k in SERVE_PROGRAMS}
        notes.append(f"serving programs by name {by_name}, by shape {by_shape}")
        if any(by_name.values()) and by_name != by_shape:
            notes.append("bench: WARNING: the serving programs counted by name and by "
                         "shape differ")
    if "step_execs" in rec:
        ops = [o for e in rec["step_execs"] for o in e.kernels()]
        by_name = {k: sum(1 for o in ops if kernel_name(o.text) == k) for k in FLASH_KERNELS}
        fwd = sum(1 for o in ops if trace.operand_count(o.text) <= 5)
        by_arity = {"forward": fwd, "backward": len(ops) - fwd}
        notes.append(f"flash kernels in the steps by name {by_name}, by operand count "
                     f"{by_arity}")
        named = {"forward": by_name["flash_fwd"],
                 "backward": by_name["flash_bwd_dq"] + by_name["flash_bwd_dkv"]}
        if any(by_name.values()) and named != by_arity:
            notes.append("bench: WARNING: the flash kernels counted by name and by "
                         "operand count differ")
    return notes


def spans_in(pt: ProgramTrace, name: str, window: tuple) -> list:
    return [s for s in pt.spans if s.name == name and window[0] <= s.start < window[1]]


def phase_idle(red, pt: ProgramTrace) -> dict:
    """Idle time of the chips in the window, ns summed over the chips, by the
    innermost span around it: a program span (``engine.*``) where there is
    one, else the harness span, else ``"no harness span"``."""
    spans = [(s, e, n) for n, s, e in red.host_spans] + [(s.start, s.end, s.name)
                                                         for s in pt.spans]
    w0, w1 = red.window
    edges = sorted({w0, w1} | {min(max(x, w0), w1) for s, e, _ in spans for x in (s, e)})
    label = ["no harness span"] * (len(edges) - 1)
    # Outer spans first, so that the innermost one names each piece; a program
    # span nested in a harness span of the same length still wins.
    for s, e, n in sorted(spans, key=lambda t: (-(t[1] - t[0]), t[2].startswith(PROGRAM_PREFIX))):
        i, j = bisect.bisect_left(edges, max(s, w0)), bisect.bisect_left(edges, min(e, w1))
        for k in range(i, j):
            label[k] = n
    out: dict = {}
    for d in red.devices:
        busy = trace.merge([(o.start, o.end) for o in d.ops])
        bounds = [w0] + [x for iv in busy for x in iv] + [w1]
        for gs, ge in zip(bounds[::2], bounds[1::2]):
            if ge <= gs:
                continue
            k = max(bisect.bisect_right(edges, gs) - 1, 0)
            while k < len(label) and edges[k] < ge:
                piece = min(ge, edges[k + 1]) - max(gs, edges[k])
                if piece > 0:
                    out[label[k]] = out.get(label[k], 0) + piece
                k += 1
    return out
