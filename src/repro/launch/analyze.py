"""Static analysis gate: ``python -m repro.launch.analyze --all --fail-on-findings``.

Runs every ``repro.analysis`` pass over the registered strategies and a
representative shape grid, entirely without devices or compilation:

  * schedule check  — rank-symbolic walk of each strategy's ``schedule_spec``
    (deadlock, matched sends, merge discipline, carry shapes, coverage);
  * comm audit      — exact per-direction wire bytes vs the registered
    ``comm_cost`` closed form, across P / head-layout / dtype points;
  * kernel lint     — VMEM footprint, grid coverage, tile divisibility and
    tile-skip soundness for representative ``FlashConfig``s and layouts;
  * overlap pre-check — jaxpr-level taint pass proving scan-body ppermutes
    do not data-depend on same-step dot_generals (``pipelines=True`` claim);
  * topology check   — per-link traffic prover (``analysis.topo_check``):
    every schedule replayed onto sample fabrics (flat NVLink pods, a
    two-pod PCIe-bridged grid, a half-duplex pod), demanding the per-link
    ledger matches the registered cost model under the graph's bandwidths.

Exit status 0 when clean; with ``--fail-on-findings``, 1 when any pass
reports a finding.  Rule catalog: ``repro.analysis.report.RULES`` and
``docs/analysis.md``.
"""

from __future__ import annotations

import argparse
import json
import sys

from repro.analysis.comm_audit import audit_strategy
from repro.analysis.kernel_lint import lint_flash_config, tile_skip_findings
from repro.analysis.report import Report
from repro.analysis.schedule_check import check_schedule_spec

# The grid is small enough to finish in seconds but hits every structural
# regime: MHA vs GQA heads, fp32 vs bf16 wire formats, P covering the P=2
# direction-tie, odd rings, and the scan-body path (P >= 4).
GRID_P = (2, 3, 4, 8)
GRID_HEADS = ((4, 4), (8, 2))  # (Hq, Hkv): MHA and 4:1 GQA
GRID_WIRE = ((4, "float32"), (2, "bfloat16"))  # (bytes_per_elem, travel_dtype)
B, D, S_LOC, WINDOW = 2, 64, 64, 96


def _strategies(names=None):
    # Importing repro.core registers the built-in strategies.
    import repro.core  # noqa: F401
    from repro.core.strategies import available_strategies, get_strategy

    pool = names or available_strategies()
    return [get_strategy(n) for n in pool]


def analyze_schedules(report: Report, descs) -> None:
    for desc in descs:
        if desc.schedule_spec is None:
            continue
        for P in GRID_P:
            spec = desc.schedule_spec(P, S_loc=S_LOC, window=WINDOW)
            report.extend(
                check_schedule_spec(spec, P, subject=f"{desc.name}[P={P}]")
            )
            report.note_checked("schedule")


def analyze_comm(report: Report, descs) -> None:
    for desc in descs:
        if desc.schedule_spec is None:
            continue
        for P in GRID_P:
            for Hq, Hkv in GRID_HEADS:
                for bpe, travel in GRID_WIRE:
                    findings = audit_strategy(
                        desc, B=B, S=S_LOC * P, Hq=Hq, Hkv=Hkv, D=D, P=P,
                        bytes_per_elem=bpe, travel_dtype=travel, window=WINDOW,
                    )
                    report.extend(findings or [])
                    report.note_checked("comm")


def analyze_kernels(report: Report) -> None:
    import numpy as np

    from repro.core.zigzag import contig_positions, zigzag_positions
    from repro.kernels.ops import FlashConfig

    for blocks in ((128, 128), (512, 512)):
        for data_bytes in (4, 2):
            for D_k in (64, 128):
                cfg = FlashConfig(
                    causal=True, block_q=blocks[0], block_k=blocks[1]
                )
                subject = (
                    f"FlashConfig(block={blocks[0]}x{blocks[1]}, D={D_k}, "
                    f"{data_bytes}B)"
                )
                report.extend(lint_flash_config(
                    cfg, Sq=1024, Sk=1024, D=D_k, data_bytes=data_bytes,
                    subject=subject,
                ))
                report.note_checked("kernel")
    # Paged-decode kernel: GQA-group x page-size grid the serving engine
    # actually runs, plus sentinel/corrupt-table probes of the index-map
    # clamp and the raw-entry skip predicate.
    from repro.analysis.kernel_lint import lint_paged_decode_config

    for group in (1, 4, 8):
        for page_size in (16, 128):
            for data_bytes in (4, 2):
                for D_k in (64, 128):
                    subject = (
                        f"PagedDecode(group={group}, page={page_size}, "
                        f"Hkv=8, D={D_k}, {data_bytes}B)"
                    )
                    report.extend(lint_paged_decode_config(
                        group=group, page_size=page_size, n_kv_heads=8,
                        n_pages=64, table_width=8, D=D_k,
                        data_bytes=data_bytes, window=WINDOW, subject=subject,
                    ))
                    report.note_checked("kernel")
    # Tile-skip soundness over the layouts the strategies actually produce.
    S = 256
    for P in (2, 4):
        layouts = {
            "zigzag": zigzag_positions,
            "contig": contig_positions,
        }
        for layout, posf in layouts.items():
            pos = np.stack([np.asarray(posf(S, P, j)) for j in range(P)])
            for window in (None, WINDOW) if layout == "contig" else (None,):
                for bq, bk in ((64, 64), (32, 32)):
                    subject = (
                        f"tile_skip[{layout}, P={P}, S={S}, "
                        f"block={bq}x{bk}, window={window}]"
                    )
                    for j in range(P):
                        report.extend(tile_skip_findings(
                            pos[j:j + 1], pos[j:j + 1], block_q=bq,
                            block_k=bk, causal=True, window=window,
                            subject=subject,
                        ))
                    report.note_checked("tile_skip")


def analyze_overlap(report: Report, descs) -> None:
    from repro.analysis.overlap_jaxpr import overlap_findings

    for desc in descs:
        if desc.schedule_spec is None or not desc.pipelines:
            continue
        for P in (4, 8):
            report.extend(overlap_findings(desc, P=P, window=WINDOW))
            report.note_checked("overlap")


def analyze_topology(report: Report, descs) -> None:
    from repro.analysis.topo_check import check_strategy_topology
    from repro.core.topology import half_duplex_pod, nvlink_pod, two_pods

    topos = (nvlink_pod(4), nvlink_pod(8), two_pods(4), half_duplex_pod(8))
    for desc in descs:
        if desc.schedule_spec is None:
            continue
        for topo in topos:
            for Hq, Hkv in GRID_HEADS:
                for bpe, travel in GRID_WIRE:
                    findings = check_strategy_topology(
                        desc, topo, B=B, S_loc=S_LOC, Hq=Hq, Hkv=Hkv, D=D,
                        bytes_per_elem=bpe, travel_dtype=travel,
                        window=WINDOW,
                    )
                    if findings is None:
                        continue
                    report.extend(findings)
                    report.note_checked("topo")


def run_analysis(
    names=None, passes=("schedule", "comm", "kernel", "overlap", "topo")
):
    """All passes over the registered strategies; returns the ``Report``."""
    report = Report()
    descs = _strategies(names)
    if "schedule" in passes:
        analyze_schedules(report, descs)
    if "comm" in passes:
        analyze_comm(report, descs)
    if "kernel" in passes:
        analyze_kernels(report)
    if "overlap" in passes:
        analyze_overlap(report, descs)
    if "topo" in passes:
        analyze_topology(report, descs)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.analyze", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--all", action="store_true",
                    help="analyze every registered strategy (default)")
    ap.add_argument("--strategy", action="append", default=None,
                    help="restrict to one strategy (repeatable)")
    ap.add_argument("--passes", default="schedule,comm,kernel,overlap,topo",
                    help="comma-separated subset of passes to run")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--verbose", action="store_true",
                    help="list per-pass check counts")
    ap.add_argument("--fail-on-findings", action="store_true",
                    help="exit 1 when any pass reports a finding")
    args = ap.parse_args(argv)

    report = run_analysis(
        names=args.strategy, passes=tuple(args.passes.split(",")),
    )
    if args.json:
        print(json.dumps({
            "findings": [
                {"rule": f.rule, "subject": f.subject, "detail": f.detail}
                for f in report.findings
            ],
            "checked": dict(report.checked),
        }, indent=2))
    else:
        print(report.render(verbose=args.verbose))
    if args.fail_on_findings and not report.ok:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
