"""metrics — the metric-catalog lint (ex scripts/check_metrics.py).

Not an AST checker: it imports every instrumented module so each
registers its families into the process-wide registry, then validates
the catalog and the exposition. scripts/check_metrics.py is now a thin
shim over `run()`; scripts/lint.py includes it unless --no-metrics.

Rules (unchanged from the PR-1 lint):
- no duplicate FULL names after namespacing (a histogram `x` and a
  counter `x_bucket` would collide in exposition)
- every metric leads with a known subsystem prefix so dashboards group
- counters end in `_total`; `_seconds`/`_bytes` metrics are histograms
  or gauges
- the exposition parses line by line
"""

from __future__ import annotations

import os
import re
from typing import List

from tendermint_tpu.analysis.engine import Finding

CHECKER_ID = "metrics"

# Every subsystem that registers metrics must appear here — a new
# instrumented module extends this set alongside docs/observability.md.
KNOWN_SUBSYSTEMS = {
    "verifier", "consensus", "mempool", "fastsync", "p2p", "merkle",
    "rpc", "node", "storage", "evidence", "lite", "telemetry", "event",
    "chaos", "mesh", "pipeline", "partset", "trace",
    "snapshot", "sync", "prune", "prof", "queue", "loop", "wire",
    "slo", "shard", "statetree", "compact", "voteagg",
    "edge", "load", "deploy", "divergence", "gc",
}

INSTRUMENTED_MODULES = [
    "tendermint_tpu.models.verifier",
    "tendermint_tpu.ops.merkle",
    "tendermint_tpu.parallel.mesh",      # tm_mesh_* sharded dispatches
    "tendermint_tpu.consensus.state",
    "tendermint_tpu.mempool.mempool",
    "tendermint_tpu.blockchain.pool",
    "tendermint_tpu.blockchain.reactor",  # tm_sync_commits_total,
                                          # tm_sync_lanes_total,
                                          # tm_sync_live_judged_total,
                                          # tm_sync_resized_total,
                                          # tm_sync_repairs_total,
                                          # tm_sync_repaired_lanes_total
    "tendermint_tpu.p2p.switch",
    "tendermint_tpu.p2p.conn.secret",    # tm_p2p_seal/open_seconds
    "tendermint_tpu.p2p.conn.mconn",     # tm_p2p_frames_per_burst
    "tendermint_tpu.types.events",       # tm_event_dropped_total
    "tendermint_tpu.rpc.core",
    "tendermint_tpu.chaos",              # tm_chaos_* fault/invariant plane
    "tendermint_tpu.pipeline",           # tm_pipeline_* hot-path stages
    "tendermint_tpu.types.part_set",     # tm_partset_build_seconds
    "tendermint_tpu.telemetry.trace",    # tm_trace_events_dropped_total,
                                         # tm_gc_* (the collector)
    "tendermint_tpu.storage.snapshot",   # tm_snapshot_* / tm_prune_*
    "tendermint_tpu.statesync.reactor",  # tm_sync_* chunk/restore plane
    "tendermint_tpu.telemetry.profile",  # tm_prof_* sampling profiler
    "tendermint_tpu.telemetry.queues",   # tm_queue_* backpressure plane
    "tendermint_tpu.p2p.conn.loop",      # tm_loop_* reactor-loop core
    "tendermint_tpu.rpc.aserver",        # tm_rpc_* async front door
    "tendermint_tpu.analysis.divergence",  # tm_divergence_* digest plane
    "tendermint_tpu.chaos.wire",         # tm_wire_* TCP fault proxy
    "tendermint_tpu.telemetry.slo",      # tm_slo_* tx-lifecycle plane
    "tendermint_tpu.shard.router",       # tm_shard_* router/height plane
    "tendermint_tpu.statetree.store",    # tm_statetree_* commit/proof plane
    "tendermint_tpu.consensus.compact",  # tm_compact_*/tm_voteagg_* gossip
    "tendermint_tpu.serving.edge",       # tm_edge_* certified read tier
    "tendermint_tpu.serving.loadgen",    # tm_load_* open-loop harness
    "tendermint_tpu.serving.deploy",     # tm_deploy_* process driver
    "tendermint_tpu.ops.ed25519",        # tm_verifier_h2d_bytes_total
    "tendermint_tpu.types.block",        # tm_verifier_commit_block_ids_total,
                                         # tm_wire_block_decodes_total
    "tendermint_tpu.types.validator_set",  # tm_verifier_vote_walks_total
    "tendermint_tpu.types.vote_set",     # tm_consensus_votes_total
    "tendermint_tpu.p2p.fuzz",           # tm_p2p_link_delay_seconds
    "tendermint_tpu.lite.certifier",     # tm_lite_windows_total,
                                         # tm_lite_transitions_total
]

# Causal span names follow the same closed-catalog discipline as metric
# families: every literal name at a span/point call site must be
# declared in telemetry.causal.SPAN_CATALOG, or dashboards and the
# trace merger silently miss it. The regex covers the three call
# shapes in the tree: causal.span/point/record(...) and the consensus
# state machine's _cspan/_cpoint/_cwait helpers.
_SPAN_NAME_RE = re.compile(
    r'(?:causal\.(?:span|point|record)|_cspan|_cpoint|_cwait)\(\s*'
    r'[\'"]([a-z0-9_.]+)[\'"]')

# The tracer's spans (telemetry/trace.py) are a closed catalogue too:
# a literal name at a trace.span/complete/instant call site (also
# reached as telemetry.* or TRACER.*) must be in trace.SPANS, or the
# per-layer metrics that read the ring by name never see it.
_TRACER_NAME_RE = re.compile(
    r'(?:trace|telemetry|TRACER)\.(?:span|complete|instant)\(\s*'
    r'[\'"]([A-Za-z0-9_.:]+)[\'"]')
# the consensus state machine's helpers write both timelines from one
# call: a mark that only the tracer takes is given by the tracer's own
# name, and those names all begin "cs:"
_CS_MARK_RE = re.compile(
    r'(?:_cspan|_cpoint|_cwait)\(\s*[\'"](cs:[A-Za-z0-9_.]+)[\'"]')

_LINE_RE = re.compile(
    r'^[a-z_][a-z0-9_]*(\{[a-z0-9_]+="(?:[^"\\]|\\.)*"'
    r'(,[a-z0-9_]+="(?:[^"\\]|\\.)*")*\})? -?[0-9.e+Inf-]+$')

_CATALOG = "tendermint_tpu/analysis/checkers/metrics.py"


def run() -> List[Finding]:
    """Import the instrumented modules and lint the registry. Findings
    carry the catalog path (the registry has no single source line)."""
    import importlib
    for mod in INSTRUMENTED_MODULES:
        importlib.import_module(mod)
    from tendermint_tpu import telemetry

    findings: List[Finding] = []

    def problem(msg: str) -> None:
        findings.append(Finding(CHECKER_ID, _CATALOG, 0, msg))

    names = telemetry.REGISTRY.names()
    if not names:
        problem("registry is empty — instrumented modules registered "
                "nothing")

    exposed = set()
    for name in names:
        fam = telemetry.REGISTRY.get(name)
        subsystem = name.split("_", 1)[0]
        if subsystem not in KNOWN_SUBSYSTEMS or "_" not in name:
            problem(f"{name}: not namespaced by a known subsystem "
                    f"(known: {sorted(KNOWN_SUBSYSTEMS)})")
        if fam.kind == "counter" and not name.endswith("_total"):
            problem(f"{name}: counters must end in _total")
        if fam.kind == "counter" and (
                name.endswith("_seconds") or name.endswith("_bytes")):
            problem(f"{name}: unit-suffixed metrics must be "
                    f"histograms or gauges")
        series = {name}
        if fam.kind == "histogram":
            series = {name + s for s in ("_bucket", "_sum", "_count")}
        elif fam.kind == "summary":
            series = {name, name + "_sum", name + "_count"}
        clash = series & exposed
        if clash:
            problem(f"{name}: exposition series collide: {clash}")
        exposed |= series

    for line in telemetry.expose().splitlines():
        if not line or line.startswith("#"):
            continue
        if not _LINE_RE.match(line):
            problem(f"unparseable exposition line: {line!r}")

    findings.extend(span_findings())

    # the table that takes a causal name to the tracer's, both sides
    from tendermint_tpu.consensus.state import _RECORDER_NAME
    from tendermint_tpu.telemetry.causal import SPAN_CATALOG
    from tendermint_tpu.telemetry.trace import SPANS
    for causal_name, span_name in _RECORDER_NAME.items():
        if causal_name not in SPAN_CATALOG:
            problem(f"consensus/state._RECORDER_NAME: {causal_name!r} not "
                    f"declared in telemetry.causal.SPAN_CATALOG")
        if span_name is not None and span_name not in SPANS:
            problem(f"consensus/state._RECORDER_NAME: {span_name!r} not "
                    f"declared in telemetry.trace.SPANS")

    run.summary = (f"{len(names)} families, {len(exposed)} "
                   f"exposed series names")
    return findings


def span_findings(root: str = "") -> List[Finding]:
    """Lint span-name call sites against their catalogues: causal
    spans against causal.SPAN_CATALOG, the tracer's against
    trace.SPANS. `root` defaults to the installed tendermint_tpu
    package tree (tests point it at fixture dirs)."""
    from tendermint_tpu.telemetry.causal import SPAN_CATALOG
    from tendermint_tpu.telemetry.trace import SPANS
    rules = ((_SPAN_NAME_RE, SPAN_CATALOG, "telemetry.causal.SPAN_CATALOG"),
             (_TRACER_NAME_RE, SPANS, "telemetry.trace.SPANS"),
             (_CS_MARK_RE, SPANS, "telemetry.trace.SPANS"))
    if not root:
        import tendermint_tpu
        pkg = os.path.dirname(os.path.abspath(tendermint_tpu.__file__))
        try:
            root = os.path.relpath(pkg)
        except ValueError:  # different drive (windows): keep absolute
            root = pkg
    findings: List[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, encoding="utf-8") as f:
                    lines = f.read().splitlines()
            except OSError:
                continue
            for i, line in enumerate(lines, 1):
                for name_re, catalog, where in rules:
                    for m in name_re.finditer(line):
                        if m.group(1) not in catalog:
                            findings.append(Finding(
                                CHECKER_ID, path, i,
                                f"span name {m.group(1)!r} not declared "
                                f"in {where}"))
    return findings


run.summary = ""
