#!/usr/bin/env python
"""tmlint runner — the whole static-analysis suite in one command.

    python scripts/lint.py               # AST checkers + knob-md drift
                                         #   + metrics registry lint
    python scripts/lint.py --no-metrics  # skip the (import-heavy)
                                         #   metrics half — pure AST
    python scripts/lint.py --taint       # add the inter-procedural
                                         #   determinism taint pass
    python scripts/lint.py --json        # also write LINT_report.json
                                         #   (runs the taint pass too)
    python scripts/lint.py --graph-stats # print call-graph resolution
                                         #   stats (flowgraph) and exit
    python scripts/lint.py --knobs-md    # (re)generate docs/knobs.md
                                         #   from the knob catalog

Exit 0 with a summary when the tree is clean; 1 with one line per
finding otherwise. Tier-1 runs this via tests/test_lint.py, so a
finding anywhere in the scan set fails the build — fix it or add a
justified `tmlint: allow(<checker>)` pragma (the pragma budget is
policed too: every pragma needs a justification and must actually
suppress something).

docs/static-analysis.md documents the checkers and pragma syntax;
docs/knobs.md is generated from tendermint_tpu/utils/knobs.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

KNOBS_MD = os.path.join(REPO, "docs", "knobs.md")
REPORT = os.path.join(REPO, "LINT_report.json")


def check_knobs_md():
    """docs/knobs.md must match the catalog byte-for-byte."""
    from tendermint_tpu.analysis.engine import Finding
    from tendermint_tpu.utils import knobs
    want = knobs.knobs_md()
    try:
        with open(KNOBS_MD, encoding="utf-8") as f:
            have = f.read()
    except FileNotFoundError:
        have = None
    if have != want:
        state = "missing" if have is None else "stale"
        return [Finding(
            "knob-registry", "docs/knobs.md", 0,
            f"docs/knobs.md is {state} — regenerate with "
            f"`python scripts/lint.py --knobs-md` and commit it")]
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const=REPORT, default=None,
                    metavar="PATH",
                    help=f"write a JSON report (default {REPORT})")
    ap.add_argument("--knobs-md", action="store_true",
                    help="write docs/knobs.md from the catalog and exit")
    ap.add_argument("--no-metrics", action="store_true",
                    help="skip the metrics registry lint (no heavy "
                         "imports; pure-AST run)")
    ap.add_argument("--taint", action="store_true",
                    help="run the inter-procedural consensus-"
                         "determinism taint pass (implied by --json)")
    ap.add_argument("--graph-stats", action="store_true",
                    help="print project call-graph resolution stats "
                         "(analysis.flowgraph) as JSON and exit")
    ap.add_argument("--max-pragmas", type=int, default=15,
                    help="fail when the tree carries more allow "
                         "pragmas than this (default 15)")
    ap.add_argument("paths", nargs="*",
                    help="scan set override (default: the package, "
                         "scripts/, benchmark/, bench_util.py, "
                         "chip_smoke.py)")
    args = ap.parse_args(argv)

    import time
    t0 = time.monotonic()

    from tendermint_tpu.utils import knobs
    if args.graph_stats:
        from tendermint_tpu.analysis.flowgraph import FlowGraph
        graph = FlowGraph.build(REPO)
        print(json.dumps(graph.stats(), indent=1, sort_keys=True))
        return 0

    if args.knobs_md:
        os.makedirs(os.path.dirname(KNOBS_MD), exist_ok=True)
        with open(KNOBS_MD, "w", encoding="utf-8") as f:
            f.write(knobs.knobs_md())
        print(f"lint: wrote {os.path.relpath(KNOBS_MD, REPO)} "
              f"({len(knobs.CATALOG)} knobs)")
        return 0

    from tendermint_tpu.analysis import run_tree
    from tendermint_tpu.analysis.checkers import all_checkers
    from tendermint_tpu.analysis.engine import Finding
    findings, pragmas, n_files = run_tree(
        REPO, paths=args.paths or None)
    findings += check_knobs_md()

    checkers_run = [c.id for c in all_checkers()] + ["pragma"]
    metrics_summary = "skipped"
    if not args.no_metrics:
        from tendermint_tpu.analysis.checkers import metrics
        findings += metrics.run()
        metrics_summary = metrics.run.summary or "failed"
        checkers_run.append("metrics")

    taint_stats = None
    if args.taint or args.json:
        from tendermint_tpu.analysis.checkers.taint import run_taint
        taint_report = run_taint(REPO)
        findings += taint_report.findings
        taint_stats = taint_report.stats
        checkers_run.append("taint")

    if len(pragmas) > args.max_pragmas:
        findings.append(Finding(
            "pragma", "(tree)", 0,
            f"{len(pragmas)} allow pragmas exceed the budget of "
            f"{args.max_pragmas} — fix code instead of suppressing"))

    if args.json:
        report = {
            "tool": "tmlint (scripts/lint.py)",
            "files_scanned": n_files,
            "checkers": checkers_run,
            "metrics": metrics_summary,
            "taint": taint_stats,
            "lint_seconds": round(time.monotonic() - t0, 3),
            "clean": not findings,
            "findings": [f.to_obj() for f in findings],
            "pragmas": [p.to_obj() for p in pragmas],
        }
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"lint: wrote {os.path.relpath(args.json, REPO)}")

    for f in sorted(findings, key=lambda f: (f.path, f.line)):
        print(f"lint: {f}")
    if findings:
        print(f"lint: FAILED — {len(findings)} finding(s) across "
              f"{n_files} files")
        return 1
    taint_summary = "skipped" if taint_stats is None else (
        f"{taint_stats['reachable_functions']} reachable fns, "
        f"{taint_stats['seam_cuts']} seam cuts")
    print(f"lint: OK — {n_files} files, "
          f"{len(pragmas)} pragma(s), metrics: {metrics_summary}, "
          f"taint: {taint_summary}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
