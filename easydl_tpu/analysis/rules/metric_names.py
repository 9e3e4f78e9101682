"""metric-name: the easydl_* naming conventions, checked at the source.

The discipline (PRs 1/9): the runtime registry (obs/registry.py) already
rejects names outside the Prometheus grammar at REGISTRATION time — but
only on paths the test run actually executes. This rule applies the same
contract, plus the repo's stricter conventions, to every registration
site statically, covering the branches the runtime lint never reaches:

* names are ``easydl_<component>_<metric>`` — lowercase
  ``[a-z0-9_]``, at least three segments, ``easydl_`` prefix (the fleet
  dashboard's namespace);
* counters end ``_total`` (rate() reads naturally, matches every
  existing counter);
* histograms end in a unit suffix (``_seconds``/``_bytes``/…) so the
  bucket scale is legible from the name;
* label names come from the shared vocabulary below — a new label is a
  cross-cutting schema decision, made once here, not ad hoc at a call
  site — and never the reserved ``le``/``quantile``/``__*``;
* a registration whose name is not statically checkable (a bare
  variable) is itself a finding: an f-string with a literal ``easydl_``
  prefix is as dynamic as the convention allows.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional

from easydl_tpu.analysis.core import (
    Finding,
    Rule,
    ScopedVisitor,
    dotted_name,
)

_REGISTER_METHODS = ("counter", "gauge", "histogram")

_NAME_RE = re.compile(r"^easydl(_[a-z0-9]+){2,}$")
_CHUNK_RE = re.compile(r"^[a-z0-9_]*$")
_LABEL_RE = re.compile(r"^[a-z][a-z0-9_]*$")

#: Unit suffixes a histogram name must end with — the bucket scale must
#: be legible from the name alone.
HISTOGRAM_UNITS = ("_seconds", "_bytes", "_examples", "_records", "_rows",
                   "_ids", "_ratio")

#: The shared label vocabulary. Adding a label here is the act of
#: declaring a new fleet-wide series dimension; every registration site
#: must draw from it.
KNOWN_LABELS = frozenset((
    "agent", "arm", "axis", "cell", "component", "fault", "generation",
    "has_plan", "job", "kind", "method", "op", "phase", "reason", "replica",
    "result", "role", "scenario", "service", "severity", "shard", "site",
    "slo", "source", "table", "target", "verb", "verdict",
))

_RESERVED_LABELS = frozenset(("le", "quantile"))

#: Every metric family the tree registers — the reference the
#: ``slo-metric-refs`` rule (analysis/rules/slo_refs.py) resolves SLO
#: series selectors against, and what tests/test_easylint.py keeps in
#: sync with the registration sites by AST scan. A name here and not in
#: the tree is stale; a registration not here is undeclared — both fail
#: the sync test. The ``easydl_rpc_{side}_*`` f-string family is listed
#: expanded (side ∈ client/server).
REGISTERED_METRICS = frozenset((
    "easydl_agent_heartbeat_rate_per_s",
    "easydl_agent_heartbeats_total",
    "easydl_agent_master_outage_seconds",
    "easydl_agent_master_outages_total",
    "easydl_agent_outage_buffered_metrics",
    "easydl_agent_phase_seconds",
    "easydl_agent_worker_samples_per_sec",
    "easydl_agent_worker_step",
    "easydl_agent_worker_step_time_seconds",
    "easydl_alert_active",
    "easydl_brain_metric_reports_total",
    "easydl_brain_plan_requests_total",
    "easydl_brain_plan_version",
    "easydl_brain_plan_workers",
    "easydl_brain_replans_total",
    "easydl_cell_fenced_pushes_total",
    "easydl_cell_promotion_seconds",
    "easydl_cell_replication_lag",
    "easydl_cell_ship_errors_total",
    "easydl_cell_ship_gaps_total",
    "easydl_cell_ship_torn_segments_total",
    "easydl_cell_ship_truncations_total",
    "easydl_cell_shipped_bytes_total",
    "easydl_cell_shipped_records_total",
    "easydl_cell_shipped_segments_total",
    "easydl_cell_shipped_snapshots_total",
    "easydl_cell_shipped_versions_total",
    "easydl_chaos_faults_injected_total",
    "easydl_chaos_scenarios_run_total",
    "easydl_controller_jobs",
    "easydl_controller_pod_ops_total",
    "easydl_controller_reconcile_seconds",
    "easydl_controller_reconcile_total",
    "easydl_feedback_bytes_total",
    "easydl_feedback_dropped_total",
    "easydl_feedback_events_total",
    "easydl_job_chip_seconds_total",
    "easydl_job_goodput_ratio",
    "easydl_loop_checkpoints_total",
    "easydl_loop_lag_seconds",
    "easydl_loop_trained_events_total",
    "easydl_master_desired_workers",
    "easydl_master_failovers_total",
    "easydl_master_generation",
    "easydl_master_journal_writes_total",
    "easydl_master_membership_size",
    "easydl_master_phase_seconds",
    "easydl_master_reconciled_agents_total",
    "easydl_master_reshapes_total",
    "easydl_master_straggler_evictions_total",
    "easydl_master_train_loss",
    "easydl_master_train_samples_per_sec",
    "easydl_master_train_step",
    "easydl_ps_client_dedup_ratio",
    "easydl_ps_pull_bytes_total",
    "easydl_ps_pull_ids_total",
    "easydl_ps_push_bytes_total",
    "easydl_ps_push_fence_rejected_total",
    "easydl_ps_push_ids_total",
    "easydl_ps_push_rejected_total",
    "easydl_ps_push_stale_route_total",
    "easydl_ps_reshard_replayed_records_total",
    "easydl_ps_reshard_rows_migrated_total",
    "easydl_ps_shard_epoch",
    "easydl_ps_shm_client_fallbacks_total",
    "easydl_ps_shm_client_ids_total",
    "easydl_ps_shm_client_pulls_total",
    "easydl_ps_table_rows",
    "easydl_ps_tier_cold_hits_total",
    "easydl_ps_tier_cold_rows",
    "easydl_ps_tier_demotions_total",
    "easydl_ps_tier_hot_rows",
    "easydl_ps_tier_promotions_total",
    "easydl_ps_wal_appends_total",
    "easydl_ps_wal_bytes_total",
    "easydl_ps_wal_deduped_pushes_total",
    "easydl_ps_wal_replayed_records_total",
    "easydl_ps_wal_retired_segments_total",
    "easydl_retrieval_candidates_total",
    "easydl_retrieval_freshness_seconds",
    "easydl_retrieval_index_rows",
    "easydl_retrieval_index_updates_total",
    "easydl_retrieval_index_version",
    "easydl_retrieval_requests_total",
    "easydl_rollout_publishes_total",
    "easydl_rollout_quarantines_total",
    "easydl_rollout_rollbacks_total",
    "easydl_rpc_client_errors_total",
    "easydl_rpc_client_latency_seconds",
    "easydl_rpc_client_requests_total",
    "easydl_rpc_server_errors_total",
    "easydl_rpc_server_latency_seconds",
    "easydl_rpc_server_requests_total",
    "easydl_scrape_attempts_total",
    "easydl_scrape_failures_total",
    "easydl_serve_batch_examples",
    "easydl_serve_cache_bytes",
    "easydl_serve_cache_evictions_total",
    "easydl_serve_cache_hits_total",
    "easydl_serve_cache_invalidations_total",
    "easydl_serve_cache_misses_total",
    "easydl_serve_examples_total",
    "easydl_serve_model_version",
    "easydl_serve_p99_seconds_recent",
    "easydl_serve_qps_recent",
    "easydl_serve_queue_examples",
    "easydl_serve_request_latency_seconds",
    "easydl_serve_requests_total",
    "easydl_serve_router_ejections_total",
    "easydl_serve_router_hedges_total",
    "easydl_serve_router_known_replicas",
    "easydl_serve_router_live_replicas",
    "easydl_serve_router_offered_qps_recent",
    "easydl_serve_router_p99_seconds_recent",
    "easydl_serve_router_readmissions_total",
    "easydl_serve_router_request_latency_seconds",
    "easydl_serve_router_requests_total",
    "easydl_serve_router_reroutes_total",
    "easydl_serve_router_routed_total",
    "easydl_swallowed_errors_total",
    "easydl_timeline_listener_errors_total",
    "easydl_train_samples_per_sec",
    "easydl_train_step",
    "easydl_worker_mesh_axis",
    "easydl_worker_mfu",
))


def _module_tuple_constants(tree: ast.Module):
    """Module-level ``NAME = ("a", "b")`` tuples — resolves the
    ``_RPC_LABELS`` indirection in utils/rpc.py."""
    out = {}
    for stmt in tree.body:
        if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and isinstance(stmt.value, (ast.Tuple, ast.List))
                and all(isinstance(e, ast.Constant)
                        and isinstance(e.value, str)
                        for e in stmt.value.elts)):
            out[stmt.targets[0].id] = tuple(
                e.value for e in stmt.value.elts)
    return out


class _Visitor(ScopedVisitor):
    def __init__(self, rule: str, path: str, tuple_consts):
        super().__init__(rule, path)
        self._tuples = tuple_consts

    # ------------------------------------------------------------- name
    def _check_name(self, node: ast.Call, kind: str) -> None:
        arg = node.args[0] if node.args else None
        name: Optional[str] = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
            if not _NAME_RE.match(name):
                self.emit(node, f"bad-name:{name}",
                          f"metric name {name!r} breaks the "
                          "easydl_<component>_<metric> lowercase "
                          "convention")
                return
        elif isinstance(arg, ast.JoinedStr):
            chunks = [v.value for v in arg.values
                      if isinstance(v, ast.Constant)]
            first = arg.values[0]
            if not (isinstance(first, ast.Constant)
                    and str(first.value).startswith("easydl_")):
                self.emit(node, "dynamic-name-prefix",
                          "f-string metric name must start with a literal "
                          "easydl_<component> prefix")
                return
            if not all(_CHUNK_RE.match(str(c)) for c in chunks):
                self.emit(node, "bad-name-chunk",
                          "literal parts of an f-string metric name must "
                          "be lowercase [a-z0-9_]")
                return
            name = "".join(str(c) for c in chunks)  # suffix still checkable
        else:
            self.emit(node, "unverifiable-name",
                      "metric name is not statically checkable — use a "
                      "literal or an f-string with a literal easydl_ "
                      "prefix")
            return
        if kind == "counter" and not name.endswith("_total"):
            self.emit(node, f"counter-no-total:{name}",
                      f"counter {name!r} must end in _total")
        if kind == "histogram" and not name.endswith(HISTOGRAM_UNITS):
            self.emit(node, f"histogram-no-unit:{name}",
                      f"histogram {name!r} must end in a unit suffix "
                      f"{HISTOGRAM_UNITS}")

    # ----------------------------------------------------------- labels
    def _label_values(self, node: ast.Call):
        lab = node.args[2] if len(node.args) > 2 else None
        for kw in node.keywords:
            if kw.arg == "labelnames":
                lab = kw.value
        if lab is None:
            return ()
        if isinstance(lab, (ast.Tuple, ast.List)):
            vals = []
            for e in lab.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    vals.append(e.value)
                else:
                    return None  # dynamic element
            return tuple(vals)
        if isinstance(lab, ast.Name):
            return self._tuples.get(lab.id)
        return None

    def _check_labels(self, node: ast.Call) -> None:
        vals = self._label_values(node)
        if vals is None:
            self.emit(node, "unverifiable-labels",
                      "labelnames are not statically checkable — use a "
                      "literal tuple (or a module-level tuple constant)")
            return
        for v in vals:
            if (not _LABEL_RE.match(v) or v in _RESERVED_LABELS
                    or v.startswith("__")):
                self.emit(node, f"bad-label:{v}",
                          f"label {v!r} breaks the lowercase grammar or "
                          "shadows a reserved Prometheus label")
            elif v not in KNOWN_LABELS:
                self.emit(node, f"unknown-label:{v}",
                          f"label {v!r} is not in the shared vocabulary "
                          "(analysis/rules/metric_names.py KNOWN_LABELS) "
                          "— declare it there (a schema decision) or "
                          "reuse an existing label")

    def visit_Call(self, node: ast.Call) -> None:
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _REGISTER_METHODS):
            recv = (dotted_name(node.func.value) or "").lower()
            # skip unrelated .counter()/.gauge() on non-registry objects:
            # every registry receiver in-tree is reg/registry/get_registry()
            looks_registry = ("reg" in recv.rsplit(".", 1)[-1]
                              or isinstance(node.func.value, ast.Call))
            if looks_registry:
                self._check_name(node, node.func.attr)
                self._check_labels(node)
        self.generic_visit(node)


class MetricNameLint(Rule):
    name = "metric-name"
    invariant = ("Every metric registration site follows the "
                 "easydl_<component>_<metric> naming scheme, counter/_total"
                 " and histogram/unit suffixes, and the shared label "
                 "vocabulary — statically, including unexecuted paths.")

    def check(self, path: str, tree: ast.Module,
              source: str) -> List[Finding]:
        v = _Visitor(self.name, path, _module_tuple_constants(tree))
        v.visit(tree)
        return v.findings
