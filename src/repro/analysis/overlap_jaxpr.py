"""Jaxpr-level overlap pre-check: ppermutes must not data-depend on
same-step dot_generals.

``launch.hlo_analysis.overlap_report`` answers this after an XLA compile;
this pass answers it straight off the jaxpr — tracing a strategy fn under
``jax.make_jaxpr(..., axis_env=[(axis, P)])`` needs no devices and no
compiler.  The taint rule mirrors the HLO pass: within one computation
context (the entry jaxpr, or one scan body), everything downstream of a
``dot_general`` — including calls whose sub-jaxpr contains one, such as the
flash ``custom_vjp`` — is compute-tainted; a ``ppermute`` with a tainted
operand is *blocked* (the transfer cannot be issued until the step's flash
finishes).

A pipelined schedule (``core/schedule.py`` with ``overlap=True``) must show
zero blocked permutes in every scan body; the ``overlap=False`` reference
mode deliberately blocks all of them (the nan_to_num marker +
optimization_barrier tie).  Cross-validated against ``overlap_report``'s
``scan_body_total`` row in ``testing/strategy_check.py``'s ``analyze`` check.
"""

from __future__ import annotations

from functools import partial

from repro.analysis.report import Finding

__all__ = ["jaxpr_overlap_report", "trace_strategy", "overlap_findings"]


def _closed_subjaxprs(eqn):
    """All sub-jaxprs hiding in an eqn's params (scan/pjit/custom_vjp/...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    found = []

    def visit(v):
        if isinstance(v, ClosedJaxpr):
            found.append(v.jaxpr)
        elif isinstance(v, Jaxpr):
            found.append(v)
        elif isinstance(v, (tuple, list)):
            for item in v:
                visit(item)

    for v in eqn.params.values():
        visit(v)
    return found


def _contains_dot(jaxpr, _memo=None) -> bool:
    if _memo is None:
        _memo = {}
    key = id(jaxpr)
    if key in _memo:
        return _memo[key]
    _memo[key] = False  # cycle guard
    result = False
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            result = True
            break
        if any(_contains_dot(sub, _memo) for sub in _closed_subjaxprs(eqn)):
            result = True
            break
    _memo[key] = result
    return result


def _analyze_context(jaxpr, name: str, rows: dict) -> None:
    """Taint-walk one computation context; recurse into scan bodies."""
    from jax.extend.core import Literal

    tainted: set = set()
    permutes = 0
    blocked = 0
    for eqn in jaxpr.eqns:
        in_vars = [v for v in eqn.invars if not isinstance(v, Literal)]
        dirty = any(v in tainted for v in in_vars)
        prim = eqn.primitive.name
        if prim == "scan":
            body = _closed_subjaxprs(eqn)[0]
            _analyze_context(body, f"scan_body[{len(rows)}]", rows)
        if prim == "ppermute":
            permutes += 1
            if dirty:
                blocked += 1
        source = prim == "dot_general" or (
            prim != "ppermute"
            and any(_contains_dot(sub) for sub in _closed_subjaxprs(eqn))
        )
        if source or dirty:
            tainted.update(eqn.outvars)
    rows[name] = {"permutes": permutes, "blocked": blocked}


def jaxpr_overlap_report(closed_jaxpr) -> dict:
    """Per-context ``{"permutes", "blocked"}`` rows plus ``total`` and
    ``scan_body_total`` aggregates (the HLO report's comparable rows)."""
    rows: dict = {}
    _analyze_context(closed_jaxpr.jaxpr, "entry", rows)
    total = {"permutes": 0, "blocked": 0}
    scan_total = {"permutes": 0, "blocked": 0}
    for name, row in rows.items():
        for k in total:
            total[k] += row[k]
            if name.startswith("scan_body"):
                scan_total[k] += row[k]
    rows["total"] = total
    rows["scan_body_total"] = scan_total
    return rows


def trace_strategy(
    desc,
    *,
    P: int,
    axis_name: str = "sp",
    B: int = 1,
    S_loc: int = 64,
    Hq: int = 4,
    Hkv: int = 4,
    D: int = 32,
    causal: bool = True,
    window: int | None = None,
    overlap: bool = True,
    block: int = 32,
):
    """Trace a strategy fn device-free under an abstract ring of ``P`` ranks.

    Hierarchical strategies (``ring_axes == 2``) trace under a two-axis
    environment factored the same way their registered spec factors ``P``
    (``core.hier2d.default_pods``), with ``axis_name`` expanded to the
    ``(pod, inner)`` pair their fn signature takes.
    """
    import jax
    import jax.numpy as jnp

    if getattr(desc, "ring_axes", 1) == 2:
        from repro.core.hier2d import default_pods

        n_pods = default_pods(P)
        axis_env = [(f"{axis_name}_pod", n_pods), (axis_name, P // n_pods)]
        bound_axis = (axis_env[0][0], axis_env[1][0])
    else:
        axis_env = [(axis_name, P)]
        bound_axis = axis_name
    fn = partial(
        desc.fn, axis_name=bound_axis, causal=causal, window=window,
        impl="xla", block_q=block, block_k=block, overlap=overlap,
    )
    f32, i32 = jnp.float32, jnp.int32
    args = (
        jax.ShapeDtypeStruct((B, S_loc, Hq, D), f32),   # q
        jax.ShapeDtypeStruct((B, S_loc, Hkv, D), f32),  # k
        jax.ShapeDtypeStruct((B, S_loc, Hkv, D), f32),  # v
        jax.ShapeDtypeStruct((B, S_loc), i32),          # q_pos
        jax.ShapeDtypeStruct((B, S_loc), i32),          # k_pos
    )
    return jax.make_jaxpr(fn, axis_env=axis_env)(*args)


def overlap_findings(desc, *, P: int, window: int | None = None):
    """OVLP-BLOCKED findings for one pipelined strategy at degree ``P``."""
    if desc.schedule_spec is None or not desc.pipelines:
        return []
    report = jaxpr_overlap_report(
        trace_strategy(desc, P=P, window=window, overlap=True)
    )
    row = report["scan_body_total"]
    if row["blocked"]:
        return [
            Finding(
                "OVLP-BLOCKED",
                f"{desc.name}[P={P}]",
                f"{row['blocked']} of {row['permutes']} scan-body "
                f"ppermute(s) data-depend on a same-step dot_general — the "
                f"pipelines=True claim does not hold on the jaxpr",
            )
        ]
    return []
