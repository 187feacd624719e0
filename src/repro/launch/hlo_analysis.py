"""Post-SPMD HLO analysis: per-device FLOPs, HBM traffic, collective bytes.

XLA's ``cost_analysis`` counts while-loop bodies ONCE, but every lax.scan
(layer loop, ring steps) is a while loop — so we parse the compiled HLO text
ourselves and multiply by the ``known_trip_count`` backend configs XLA leaves
on each while op.  For every computation we accumulate, with its loop
multiplier:

  * ``dot_flops``  — 2*M*N*K per dot (batch dims included); the MXU term;
  * ``dot_bytes``  — lhs+rhs+out bytes per dot: an explicit no-fusion HBM
    traffic model (upper bound; consistent across variants);
  * ``dot_bytes_fused`` — the headline memory-traffic model: only operands
    coming from *outside the computation* (parameters / loop carries, i.e.
    HBM-resident tensors: weights, activations entering a scan step) are
    charged, and a dot's result is charged only when it feeds the computation
    root (escapes to HBM).  Intermediates consumed in place model VMEM
    residency — matching what the Pallas kernel achieves on real hardware;
  * collective bytes by op kind, and for ``collective-permute`` the ring
    *direction and hop distance* recovered from ``source_target_pairs`` —
    this is what quantifies TokenRing's bidirectional win and the O(P^2)
    hop-bytes of the faithful full-mesh schedule on a torus.

Ring cost model (per device, per direction, P = ring size):
  permute(shift d, msg B):  B * min(d, P-d)  charged to the shorter direction
  all-gather(out B):        B * (P-1)/P / 2  per direction (bidir ring)
  reduce-scatter(in B):     B * (P-1)/P / 2
  all-reduce(buf B):        B * (P-1)/P      per direction (RS+AG)
  all-to-all(buf B):        B * P / 8        per direction (uniform routing)

The collective roofline term is ``max(fwd, bwd) / link_bw`` — a schedule that
loads both directions evenly halves it, which is the paper's §3.1 claim made
measurable.
"""

from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field

__all__ = ["analyze_hlo", "overlap_report", "HloStats"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_WHILE_RE = re.compile(
    r"while\(.*?\).*?body=%?([\w.\-]+).*?known_trip_count\":\{\"n\":\"(\d+)\"",
    re.DOTALL,
)
# TPU HLO text carries no known_trip_count: the trip count is read off the
# loop's condition computation instead (see _cond_trip_count).
_WHILE_COND_RE = re.compile(
    r"\bwhile\(.*?condition=%?([\w.\-]+), body=%?([\w.\-]+)"
)
_CALLS_RE = re.compile(
    r"(?:body|condition|to_apply|branch_computations=\{)[=%]?%?([\w.\-]+)"
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(type_str):
    """Bytes of 'f32[1,2,3]' (tuples: sum of elements)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_elems(type_str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, 0
    dt, dims = m.groups()
    n = 1
    shape = []
    for d in dims.split(","):
        if d:
            shape.append(int(d))
            n *= int(d)
    return dt, shape


@dataclass
class HloStats:
    dot_flops: float = 0.0
    dot_bytes: float = 0.0
    dot_bytes_fused: float = 0.0
    collective_bytes: dict = field(default_factory=lambda: defaultdict(float))
    # per-direction link-bytes under the ring model
    link_bytes_fwd: float = 0.0
    link_bytes_bwd: float = 0.0
    permute_hop_bytes: float = 0.0
    n_collectives: int = 0

    def as_dict(self):
        return {
            "dot_flops": self.dot_flops,
            "dot_bytes": self.dot_bytes,
            "dot_bytes_fused": self.dot_bytes_fused,
            "collective_bytes": dict(self.collective_bytes),
            "link_bytes_fwd": self.link_bytes_fwd,
            "link_bytes_bwd": self.link_bytes_bwd,
            "permute_hop_bytes": self.permute_hop_bytes,
            "n_collectives": self.n_collectives,
        }


def _split_computations(hlo: str):
    """name -> list of instruction lines."""
    comps = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if (stripped.startswith("%") or stripped.startswith("ENTRY")) and stripped.endswith("{"):
            header = stripped
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", header)
            cur = m.group(1)
            comps[cur] = []
        elif stripped.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(stripped)
    return comps


def _cond_trip_count(lines) -> int:
    """Trip count of a ``lax.scan``/``fori_loop`` while loop from its
    condition computation: JAX's counted loops run ``i = 0; i < N`` with
    ``N`` the condition's only integer scalar constant.  1 when the
    condition is not of that form."""
    consts = re.findall(r"[su]32\[\][^=]*?constant\((\d+)\)", "\n".join(lines))
    return int(consts[0]) if len(consts) == 1 else 1


def _multipliers(comps):
    """computation name -> execution count (product of enclosing trip counts)."""
    # map computation -> (child computation, trip) for while bodies; and
    # computation -> children for other calls (fusion/scan cond/branches).
    entry = None
    for name in comps:
        if name.startswith("main") or entry is None:
            pass
    # Build call graph with weights.
    edges = defaultdict(list)  # parent -> [(child, weight)]
    for name, lines in comps.items():
        for ln in lines:
            wm = _WHILE_RE.search(ln)
            if wm:
                body, n = wm.group(1), int(wm.group(2))
                edges[name].append((body, n))
                # condition executes n+1 times but holds no collectives/dots
                continue
            wc = _WHILE_COND_RE.search(ln)
            if wc:
                cond, body = wc.groups()
                edges[name].append((body, _cond_trip_count(comps.get(cond, []))))
                continue
            for cm in re.finditer(r"(?:body|condition|to_apply)=%?([\w.\-]+)", ln):
                child = cm.group(1)
                edges[name].append((child, 1))
            bm = re.search(r"branch_computations=\{([^}]*)\}", ln)
            if bm:
                for child in re.findall(r"%?([\w.\-]+)", bm.group(1)):
                    edges[name].append((child, 1))
            cm2 = re.search(r"calls=%?([\w.\-]+)", ln)
            if cm2:
                edges[name].append((cm2.group(1), 1))

    # Roots: computations nobody calls (ENTRY).
    called = {c for kids in edges.values() for c, _ in kids}
    mult = {}

    def visit(name, m):
        mult[name] = mult.get(name, 0.0) + m
        for child, w in edges.get(name, []):
            if child in comps:
                visit(child, m * w)

    for name in comps:
        if name not in called:
            visit(name, 1.0)
    return mult


def _dot_flops_bytes(line, shapes, external, root_operands):
    """FLOPs, no-fusion bytes, and fused-model bytes for a dot line."""
    dm = _DEF_RE.match(line)
    if not dm:
        return 0.0, 0.0, 0.0
    name, rhs = dm.group(1), dm.group(2)
    dt, out_shape = _first_shape_elems(rhs)
    out_elems = math.prod(out_shape) if out_shape else 0
    om = re.search(r"dot\(([^)]*)\)", rhs)
    if not om:
        return 0.0, 0.0, 0.0
    ops = [o.strip().lstrip("%") for o in om.group(1).split(",")]
    lhs_shape = shapes.get(ops[0], (None, []))[1] if ops else []
    rhs_shape = shapes.get(ops[1], (None, []))[1] if len(ops) > 1 else []
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rhs)
    k = 1
    if cm and lhs_shape:
        for d in cm.group(1).split(","):
            if d and int(d) < len(lhs_shape):
                k *= lhs_shape[int(d)]
    flops = 2.0 * out_elems * k
    bpe = _DTYPE_BYTES.get(dt, 4)
    lhs_b = (
        math.prod(lhs_shape)
        * _DTYPE_BYTES.get(shapes.get(ops[0], ("f32", []))[0], 4)
        if lhs_shape
        else 0
    )
    rhs_b = (
        math.prod(rhs_shape)
        * _DTYPE_BYTES.get(shapes.get(ops[1], ("f32", []))[0], 4)
        if rhs_shape
        else 0
    )
    out_b = out_elems * bpe
    total = float(lhs_b + rhs_b + out_b)
    fused = 0.0
    if ops and ops[0] in external:
        fused += lhs_b
    if len(ops) > 1 and ops[1] in external:
        fused += rhs_b
    if name in root_operands:
        fused += out_b
    return flops, total, fused


def _ring_shift(pairs, world):
    """If source_target_pairs is a uniform ring shift, return it (else None)."""
    if not pairs:
        return None
    shifts = {(dst - src) % world for src, dst in pairs}
    if len(shifts) == 1:
        return shifts.pop()
    return None


def _operand_refs(rhs: str) -> list[str]:
    """Instruction names referenced as *data operands* of an HLO line.

    Attached computations (``body=``, ``condition=``, ``calls=``,
    ``to_apply=``, ``branch_computations=``) are stripped first so they never
    create false data edges; everything else ``%``-referenced is an operand.
    (Tuple-typed instructions put parentheses inside the *type*, so slicing
    at the first ``)`` would miss e.g. ``get-tuple-element((...) %while.16)``.)
    """
    cut = re.sub(r"(?:body|condition|to_apply|calls)=%?[\w.\-]+", "", rhs)
    cut = re.sub(r"branch_computations=\{[^}]*\}", "", cut)
    return [r.lstrip("%") for r in re.findall(r"%([\w.\-]+)", cut)]


_CALLED_COMP_RE = re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}"
)


def overlap_report(hlo: str) -> dict:
    """Per-computation dependency audit of collective-permutes vs dots.

    The schedule executor's pipelining claim (docs/overlap.md) is a
    *dependency-graph* property: in a pipelined step, no transfer consumes
    anything the step computed, so within the loop-body computation no
    ``collective-permute`` operand may transitively reach a ``dot`` (or a
    fusion/call that contains one).  The legacy merge→rotate chain — and the
    executor's ``overlap=False`` barrier mode — puts every permute downstream
    of the step's flash.

    Returns ``{computation: {"permutes": n, "compute_blocked": m}}`` for every
    computation holding at least one permute, plus a ``"total"`` row and a
    ``"scan_body_total"`` row restricted to while-loop body computations.

    The scan-body row is the crisp assertion: a pipelined schedule's loop
    body must show ``compute_blocked == 0`` and the sequential reference mode
    must show every body permute blocked (``strategy_check overlap`` pins
    both).  Unrolled prologue/epilogue steps live inlined in ENTRY where
    *cross*-step dependencies (real and fine — step ``i+1`` consumes what
    step ``i`` received) are indistinguishable from same-step ones, so for
    fully unrolled schedules (``tokenring_faithful``) pipelining shows up as
    a strictly *lower* total, not zero.
    """
    comps = _split_computations(hlo)

    # A computation "has compute" if it holds a dot — or a custom-call, the
    # form a Pallas flash kernel takes on TPU — transitively through the
    # computations it calls (CPU HLO wraps dots in fusions).
    calls: dict[str, set[str]] = {}
    has_dot_direct: set[str] = set()
    for name, lines in comps.items():
        kids: set[str] = set()
        for ln in lines:
            if re.search(r"\b(?:dot[.\d]*|custom-call[.\d]*)\(", ln):
                has_dot_direct.add(name)
            for m in _CALLED_COMP_RE.finditer(ln):
                if m.group(1):
                    kids.add(m.group(1))
                elif m.group(2):
                    kids.update(
                        c.lstrip("%") for c in re.findall(r"%?([\w.\-]+)", m.group(2))
                    )
        calls[name] = kids

    def comp_has_dot(name: str, seen: frozenset = frozenset()) -> bool:
        if name in has_dot_direct:
            return True
        if name in seen:
            return False
        return any(
            comp_has_dot(c, seen | {name}) for c in calls.get(name, ()) if c in comps
        )

    while_bodies: set[str] = set()
    for lines in comps.values():
        for ln in lines:
            wm = re.search(r"\bwhile\(.*?body=%?([\w.\-]+)", ln)
            if wm:
                while_bodies.add(wm.group(1))

    report: dict[str, dict] = {}
    total = {"permutes": 0, "compute_blocked": 0}
    body_total = {"permutes": 0, "compute_blocked": 0}
    for name, lines in comps.items():
        defs: dict[str, list[str]] = {}
        tainted: set[str] = set()  # instrs that are/contain/see compute
        permutes: list[tuple[str, list[str]]] = []
        for ln in lines:
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            nm, rhs = dm.group(1), dm.group(2)
            refs = _operand_refs(rhs)
            defs[nm] = refs
            is_compute = bool(
                re.search(r"\b(?:dot[.\d]*|custom-call[.\d]*)\(", rhs)
            )
            if not is_compute:
                # any called computation (fusion, nested while body/cond,
                # branches) that transitively holds a dot taints this instr
                for cm in _CALLED_COMP_RE.finditer(rhs):
                    called = [cm.group(1)] if cm.group(1) else re.findall(
                        r"%?([\w.\-]+)", cm.group(2) or ""
                    )
                    if any(c in comps and comp_has_dot(c) for c in called):
                        is_compute = True
                        break
            if is_compute:
                tainted.add(nm)
            # sync form on CPU; async `-start` half on TPU (the `-done`
            # consumes the start, so counting starts alone is exact)
            if re.search(r"\bcollective-permute(?:-start)?[.\d]*\(", rhs):
                permutes.append((nm, refs))
        if not permutes:
            continue

        # Propagate taint forward through the (acyclic) local def-use chains:
        # an instruction is tainted if any operand is (iterative — HLO
        # computations can be thousands of instructions deep).
        changed = True
        while changed:
            changed = False
            for nm, refs in defs.items():
                if nm not in tainted and any(r in tainted for r in refs):
                    tainted.add(nm)
                    changed = True

        blocked = sum(1 for _, refs in permutes if any(r in tainted for r in refs))
        report[name] = {"permutes": len(permutes), "compute_blocked": blocked}
        total["permutes"] += len(permutes)
        total["compute_blocked"] += blocked
        if name in while_bodies:
            body_total["permutes"] += len(permutes)
            body_total["compute_blocked"] += blocked
    report["total"] = total
    report["scan_body_total"] = body_total
    return report


def analyze_hlo(hlo: str, *, world: int, ring_sizes: dict | None = None) -> HloStats:
    """Analyze compiled (post-SPMD) HLO text.

    ``world``: total devices.  ``ring_sizes``: optional map collective op name
    prefix -> ring size; defaults derive shift distance modulo the *group*
    size inferred from the permute pairs themselves.
    """
    comps = _split_computations(hlo)
    mult = _multipliers(comps)
    stats = HloStats()

    _PASSTHRU = (
        "convert(", "reshape(", "transpose(", "copy(", "bitcast(",
        "slice(", "dynamic-slice(",
    )

    for cname, lines in comps.items():
        m = mult.get(cname, 1.0)
        # name -> (dtype, shape); plus "external" = HBM-resident provenance
        shapes = {}
        external = set()
        root_operands = set()
        for ln in lines:
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            nm, rhs = dm.group(1), dm.group(2)
            dt, shp = _first_shape_elems(rhs)
            if dt:
                shapes[nm] = (dt, shp)
            opm = re.search(r"\}\s*([\w.\-]+)\(", rhs) or re.search(
                r"\]\s*([\w.\-]+)\(", rhs
            )
            opname = (opm.group(1) + "(") if opm else ""
            if "parameter(" in rhs or "get-tuple-element" in rhs or "iota(" in rhs or "constant(" in rhs:
                external.add(nm)
            elif opname in _PASSTHRU:
                refs = [r.lstrip("%") for r in re.findall(r"%([\w.\-]+)", rhs)]
                if refs and all(r in external for r in refs):
                    external.add(nm)
            if ln.lstrip().startswith("ROOT"):
                root_operands.update(r.lstrip("%") for r in re.findall(r"%([\w.\-]+)", rhs))

        for ln in lines:
            if " dot(" in ln or "= dot(" in ln:
                f, b, bf = _dot_flops_bytes(ln, shapes, external, root_operands)
                stats.dot_flops += m * f
                stats.dot_bytes += m * b
                stats.dot_bytes_fused += m * bf
                continue
            kind = next((c for c in _COLLECTIVES if f" {c}(" in ln or f"= {c}(" in ln or ln.startswith(c)), None)
            if kind is None:
                # also catch '%all-reduce.1 = ... all-reduce(' patterns
                kind = next((c for c in _COLLECTIVES if re.search(rf"\b{c}[.\d]*\(", ln)), None)
            # TPU splits a permute into an async start/done pair; the start's
            # tuple type leads with the operand buffer (the bytes sent).
            async_start = kind is None and " collective-permute-start(" in ln
            if async_start:
                kind = "collective-permute"
            if kind is None:
                continue
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            if async_start:
                first = _SHAPE_RE.search(dm.group(2))
                nbytes = _shape_bytes(first.group(0)) if first else 0
            else:
                nbytes = _shape_bytes(dm.group(2).split(" ", 1)[0]) or _shape_bytes(
                    dm.group(2)
                )
            stats.n_collectives += 1
            stats.collective_bytes[kind] += m * nbytes

            if kind == "collective-permute":
                pm = _PAIRS_RE.search(ln)
                pairs = (
                    [(int(a), int(b)) for a, b in _PAIR_RE.findall(pm.group(1))]
                    if pm
                    else []
                )
                # A permute over one mesh axis decomposes into independent
                # subrings (one per slice of the other axes).  Classify the
                # shift WITHIN each connected component, then charge each
                # device's bytes to the shorter ring direction.
                comps_uf = {}

                def find(x):
                    while comps_uf.get(x, x) != x:
                        comps_uf[x] = comps_uf.get(comps_uf[x], comps_uf[x])
                        x = comps_uf[x]
                    return x

                for a, b in pairs:
                    comps_uf.setdefault(a, a)
                    comps_uf.setdefault(b, b)
                    ra, rb = find(a), find(b)
                    if ra != rb:
                        comps_uf[ra] = rb
                groups = defaultdict(list)
                for a, b in pairs:
                    groups[find(a)].append((a, b))
                shift_counts = defaultdict(int)  # (shift, gsize) -> n pairs
                for grp in groups.values():
                    members = sorted({r for pr in grp for r in pr})
                    gsize = len(members)
                    index = {r: i for i, r in enumerate(members)}
                    for src, dst in grp:
                        sh = (index[dst] - index[src]) % gsize
                        shift_counts[(sh, gsize)] += 1
                total_pairs = sum(shift_counts.values()) or 1
                for (sh, gsize), cnt in shift_counts.items():
                    frac = cnt / total_pairs
                    hops = min(sh, gsize - sh) if gsize else 0
                    forward = sh != 0 and sh <= gsize - sh
                    hop_b = m * nbytes * hops * frac
                    stats.permute_hop_bytes += hop_b
                    if forward:
                        stats.link_bytes_fwd += hop_b
                    else:
                        stats.link_bytes_bwd += hop_b
            elif kind == "all-reduce":
                per_dir = m * nbytes * (world - 1) / max(world, 1)
                stats.link_bytes_fwd += per_dir
                stats.link_bytes_bwd += per_dir
            elif kind in ("all-gather", "reduce-scatter"):
                per_dir = m * nbytes * (world - 1) / max(world, 1) / 2
                stats.link_bytes_fwd += per_dir
                stats.link_bytes_bwd += per_dir
            elif kind == "all-to-all":
                per_dir = m * nbytes * world / 8
                stats.link_bytes_fwd += per_dir
                stats.link_bytes_bwd += per_dir

    return stats
