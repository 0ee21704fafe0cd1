"""EXPLAIN ANALYZE: execute a plan and render the annotated operator tree.

Parity role: Spark's `EXPLAIN ANALYZE` / the SQL-tab per-node SQLMetrics
view over the reference engine.  `explain_analyze` runs the query through
the production task path, merges the per-partition metric trees into one
query-level profile (MetricNode.merge_from), snapshots XLA compile and
host<->device transfer counters around the run, and renders the result as
an annotated plan text or a JSON-ready dict.

The profile is registered with the observability service
(bridge/profiling.record_profile), so the same data is retrievable over
HTTP at /profile/<qid> and folded into /metrics.prom.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Union

from blaze_tpu.bridge.metrics import BASELINE_METRICS, MetricNode


def _fmt_ns(ns: int) -> str:
    if ns >= 1_000_000_000:
        return f"{ns / 1e9:.2f}s"
    if ns >= 1_000_000:
        return f"{ns / 1e6:.2f}ms"
    if ns >= 1_000:
        return f"{ns / 1e3:.1f}us"
    return f"{ns}ns"


def _fmt_bytes(n: int) -> str:
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if n >= div:
            return f"{n / div:.1f}{unit}"
    return f"{n}B"


def format_speculation_footer(x) -> Optional[str]:
    """The explain-analyze "speculation:" footer for one run's engine
    stats, or None when no hedging (or rejected loser commit) happened
    — speculation is off by default and the profile must stay
    byte-identical then."""
    if not any(x.get(k) for k in ("speculation_attempts",
                                  "speculation_wins",
                                  "speculation_loser_commits_rejected",
                                  "speculation_commit_races")):
        return None
    return (
        f"speculation: waves={x.get('speculation_waves', 0)} "
        f"attempts={x.get('speculation_attempts', 0)} "
        f"wins={x.get('speculation_wins', 0)} "
        f"losers_cancelled="
        f"{x.get('speculation_losers_cancelled', 0)} "
        f"loser_commits_rejected="
        f"{x.get('speculation_loser_commits_rejected', 0)} "
        f"commit_races={x.get('speculation_commit_races', 0)} "
        f"duplicate_commits="
        f"{x.get('speculation_duplicate_commits', 0)}")


def format_work_sharing_footer(x) -> Optional[str]:
    """The explain-analyze "work sharing:" footer (result/subplan cache,
    single-flight, shared scan decode), or None when the run touched
    none of it — the cache is off by default and the profile must stay
    byte-identical then."""
    if not any(x.get(k) for k in (
            "result_cache_hits", "result_cache_misses",
            "result_cache_puts", "subplan_cache_hits",
            "subplan_cache_misses", "single_flight_coalesces",
            "scan_share_hits", "scan_share_misses")):
        return None

    def rate(hits: int, misses: int) -> str:
        total = hits + misses
        return f"{hits / total:.0%}" if total else "n/a"

    rc_h = x.get("result_cache_hits", 0)
    rc_m = x.get("result_cache_misses", 0)
    sp_h = x.get("subplan_cache_hits", 0)
    sp_m = x.get("subplan_cache_misses", 0)
    ss_h = x.get("scan_share_hits", 0)
    ss_m = x.get("scan_share_misses", 0)
    return (
        f"work sharing: result={rc_h}/{rc_h + rc_m} "
        f"({rate(rc_h, rc_m)}) "
        f"subplan={sp_h}/{sp_h + sp_m} ({rate(sp_h, sp_m)}) "
        f"coalesced={x.get('single_flight_coalesces', 0)} "
        f"promoted={x.get('single_flight_promotions', 0)} "
        f"scan_share={ss_h}/{ss_h + ss_m} ({rate(ss_h, ss_m)}) "
        f"saved={_fmt_bytes(x.get('scan_share_bytes_saved', 0))} "
        f"evictions={x.get('result_cache_evictions', 0)} "
        f"invalidations={x.get('result_cache_invalidations', 0)}")


def format_aqe_footer(x) -> Optional[str]:
    """The explain-analyze "aqe:" footer (runtime rewrites and
    history-seeded planning), or None when adaptive execution never
    fired — AQE is off by default and the profile must stay
    byte-identical then."""
    if not (x.get("aqe_rewrites") or x.get("aqe_history_seeds")):
        return None
    return (
        f"aqe: rewrites={x.get('aqe_rewrites', 0)} "
        f"broadcast={x.get('aqe_broadcast_switches', 0)} "
        f"coalesced={x.get('aqe_partitions_coalesced', 0)} "
        f"skew_splits={x.get('aqe_skew_splits', 0)} "
        f"history_seeds={x.get('aqe_history_seeds', 0)} "
        f"stages_elided={x.get('aqe_stages_elided', 0)} "
        f"saved={_fmt_bytes(x.get('aqe_bytes_saved', 0))}")


def format_encodings_footer(x) -> Optional[str]:
    """The explain-analyze "encodings:" footer (dictionary-encoded
    strings and scaled-int/limb decimals on the device lanes), or None
    when no encoding lane fired — the encoding knobs are off by default
    and the profile must stay byte-identical then."""
    ev = (x.get("host_evictions_string", 0)
          + x.get("host_evictions_decimal", 0)
          + x.get("host_evictions_other", 0))
    if not (x.get("dict_encoded_columns")
            or x.get("decimal_scaled_int32_dispatches")
            or x.get("decimal_scaled_int64_dispatches")
            or x.get("decimal_limb_dispatches") or ev):
        return None
    return (
        f"encodings: dict_cols={x.get('dict_encoded_columns', 0)} "
        f"remaps={x.get('dict_exchange_remaps', 0)} "
        f"dec_i32={x.get('decimal_scaled_int32_dispatches', 0)} "
        f"dec_i64={x.get('decimal_scaled_int64_dispatches', 0)} "
        f"dec_limb={x.get('decimal_limb_dispatches', 0)} "
        f"evictions=string:{x.get('host_evictions_string', 0)}"
        f"/decimal:{x.get('host_evictions_decimal', 0)}"
        f"/other:{x.get('host_evictions_other', 0)}")


def format_bottleneck_footer(report) -> Optional[str]:
    """The explain-analyze "bottleneck:" footer from a
    bridge/critical_path.bottleneck_report dict, or None when no spans
    were traced — tracing is off by default and the profile must stay
    byte-identical then."""
    if not report or not report.get("span_count"):
        return None
    cats = report.get("categories") or {}
    parts = [f"{k}={cats[k]:.3f}s" for k in sorted(cats) if cats.get(k)]
    head = f"bottleneck: wall={report.get('wall_s', 0):.3f}s"
    dom = report.get("dominant")
    if dom:
        head += (f" dominant={dom} "
                 f"({report.get('dominant_fraction', 0):.0%})")
    return head + ((" " + " ".join(parts)) if parts else "")


def _node_line(node: MetricNode) -> str:
    v = node.values
    total = v.get("elapsed_compute_ns", 0)
    self_ns = max(0, total - sum(c.values.get("elapsed_compute_ns", 0)
                                 for c in node.children))
    parts = [f"rows={v.get('output_rows', 0)}",
             f"batches={v.get('output_batches', 0)}",
             f"time={_fmt_ns(total)}"]
    if node.children:
        parts.append(f"(self {_fmt_ns(self_ns)})")
    if v.get("mem_used", 0):
        parts.append(f"mem={_fmt_bytes(v['mem_used'])}")
    if v.get("spilled_bytes", 0):
        parts.append(f"spilled={_fmt_bytes(v['spilled_bytes'])}")
    if v.get("io_bytes", 0):
        parts.append(f"io={_fmt_bytes(v['io_bytes'])}")
    for k in sorted(v):
        if k not in BASELINE_METRICS and v[k]:
            parts.append(f"{k}={v[k]}")
    return f"{node.name or '?'}  [{' '.join(parts)}]"


def render_tree(node: MetricNode, indent: str = "", last: bool = True,
                root: bool = True) -> List[str]:
    if root:
        lines = [_node_line(node)]
        child_indent = ""
    else:
        branch = "└─ " if last else "├─ "
        lines = [indent + branch + _node_line(node)]
        child_indent = indent + ("   " if last else "│  ")
    for i, c in enumerate(node.children):
        lines.extend(render_tree(c, child_indent,
                                 last=(i == len(node.children) - 1),
                                 root=False))
    return lines


@dataclass
class QueryProfile:
    """One executed query's merged profile (the /profile/<qid> payload)."""
    query_id: str
    wall_ns: int
    tree: MetricNode
    partitions: int
    exec_mode: str
    xla: Dict[str, int] = field(default_factory=dict)
    kernels: Dict[str, dict] = field(default_factory=dict)
    # programs JAX was asked for while the query ran, by (program, call
    # site), the five with most seconds (xla_stats.program_load_summary);
    # empty for a warm query
    programs: List[dict] = field(default_factory=list)
    placement: str = ""
    output_rows: int = 0
    # critical-path category attribution (bridge/critical_path.py
    # bottleneck_report over the run's spans); None when tracing was off
    bottleneck: Optional[dict] = None
    # result table, only populated under keep_result=True; NOT serialized
    result: Optional[Any] = None

    def to_dict(self) -> dict:
        d = {
            "query_id": self.query_id,
            "wall_ns": self.wall_ns,
            "tree": self.tree.to_dict(),
            "partitions": self.partitions,
            "exec_mode": self.exec_mode,
            "xla": dict(self.xla),
            "kernels": {k: dict(v) for k, v in self.kernels.items()},
            "placement": self.placement,
            "output_rows": self.output_rows,
        }
        if self.programs:
            d["programs"] = [dict(p) for p in self.programs]
        if self.bottleneck is not None:
            d["bottleneck"] = self.bottleneck
        return d

    def render_text(self) -> str:
        lines = [f"== query profile {self.query_id} "
                 f"(wall {_fmt_ns(self.wall_ns)}, "
                 f"{self.partitions} partition(s), "
                 f"mode={self.exec_mode}, placement={self.placement}) =="]
        lines.extend(render_tree(self.tree))
        x = self.xla
        lines.append(
            f"XLA: compiles={x.get('total_compiles', 0)} "
            f"cache_hits={x.get('total_cache_hits', 0)} "
            f"compile_time={_fmt_ns(x.get('total_compile_ns', 0))}"
            + "".join(f" {p['program']}@{p['site']}"
                      f"({_fmt_ns(int(p['seconds'] * 1e9))})"
                      for p in self.programs))
        churny = [f"{k} ({v['distinct_signatures']} signatures)"
                  for k, v in sorted(self.kernels.items())
                  if v.get("shape_churn")]
        if churny:
            lines.append("shape-churn kernels: " + ", ".join(churny))
        lines.append(
            f"transfers: h2d={_fmt_bytes(x.get('h2d_bytes', 0))} "
            f"({x.get('h2d_transfers', 0)}) "
            f"d2h={_fmt_bytes(x.get('d2h_bytes', 0))} "
            f"({x.get('d2h_transfers', 0)})")
        if x.get("bucket_batches"):
            lines.append(
                f"batch shaping: bucketed_caps={x.get('bucket_batches', 0)} "
                f"new_buckets={x.get('distinct_buckets', 0)} "
                f"pad_rows={x.get('bucket_pad_rows', 0)}")
        if x.get("prefetch_batches") or x.get("prefetch_wait_ns"):
            lines.append(
                f"prefetch: batches={x.get('prefetch_batches', 0)} "
                f"consumer_wait={_fmt_ns(x.get('prefetch_wait_ns', 0))} "
                f"({x.get('prefetch_waits', 0)} waits)")
        if x.get("scan_row_groups"):
            lines.append(
                f"scan: groups={x.get('scan_row_groups', 0)} "
                f"pruned={x.get('scan_row_groups_pruned', 0)}")
        if (x.get("expr_fused_batches") or x.get("expr_eager_batches")
                or x.get("expr_programs_built")):
            looked_up = (x.get("expr_programs_built", 0)
                         + x.get("expr_program_cache_hits", 0))
            rate = (x.get("expr_program_cache_hits", 0) / looked_up
                    if looked_up else 0.0)
            lines.append(
                f"expr programs: built={x.get('expr_programs_built', 0)} "
                f"cache_hits={x.get('expr_program_cache_hits', 0)} "
                f"(hit_rate={rate:.2f}) "
                f"fused_batches={x.get('expr_fused_batches', 0)} "
                f"eager_batches={x.get('expr_eager_batches', 0)} "
                f"evictions={x.get('expr_program_evictions', 0)}")
        if x.get("partial_agg_skip_events") or x.get("partial_agg_probe_rows"):
            probe_rows = x.get("partial_agg_probe_rows", 0)
            ratio = (x.get("partial_agg_probe_groups", 0) / probe_rows
                     if probe_rows else 0.0)
            events = x.get("partial_agg_skip_events", 0)
            switch_row = (x.get("partial_agg_switch_rows", 0) // events
                          if events else 0)
            lines.append(
                f"partial agg: probe_ratio={ratio:.2f} "
                f"skip_events={events} switch_row={switch_row} "
                f"passed_rows={x.get('partial_agg_skipped_rows', 0)} "
                f"spill_switches={x.get('partial_agg_spill_switches', 0)}")
        if any(x.get(k) for k in ("task_retries", "task_failures",
                                  "fetch_failures", "stage_recoveries",
                                  "faults_injected")):
            lines.append(
                f"fault tolerance: attempts={x.get('task_attempts', 0)} "
                f"retries={x.get('task_retries', 0)} "
                f"retry_wait={_fmt_ns(x.get('task_retry_wait_ns', 0))} "
                f"failures={x.get('task_failures', 0)} "
                f"fetch_failures={x.get('fetch_failures', 0)} "
                f"recoveries={x.get('stage_recoveries', 0)} "
                f"recovered_map_tasks={x.get('recovered_map_tasks', 0)} "
                f"faults_injected={x.get('faults_injected', 0)}")
        if any(x.get(k) for k in ("worker_tasks", "worker_crashes",
                                  "worker_hangs", "worker_blacklisted")):
            lines.append(
                f"workers: tasks={x.get('worker_tasks', 0)} "
                f"spawns={x.get('worker_spawns', 0)} "
                f"crashes={x.get('worker_crashes', 0)} "
                f"hangs={x.get('worker_hangs', 0)} "
                f"restarts={x.get('worker_restarts', 0)} "
                f"blacklisted={x.get('worker_blacklisted', 0)} "
                f"cancels={x.get('worker_cancels', 0)}")
        spec_line = format_speculation_footer(x)
        if spec_line is not None:
            lines.append(spec_line)
        ws_line = format_work_sharing_footer(x)
        if ws_line is not None:
            lines.append(ws_line)
        aqe_line = format_aqe_footer(x)
        if aqe_line is not None:
            lines.append(aqe_line)
        enc_line = format_encodings_footer(x)
        if enc_line is not None:
            lines.append(enc_line)
        if any(x.get(k) for k in ("shuffle_resident_rows",
                                  "shuffle_file_rows")):
            # a map task's committed rows by the tier they took; `spilled`
            # are resident rows a spill wrote to files later
            lines.append(
                f"exchange tiers: resident={x.get('shuffle_resident_rows', 0)}"
                f" rows ({_fmt_bytes(x.get('shuffle_resident_bytes', 0))}) "
                f"file={x.get('shuffle_file_rows', 0)} rows "
                f"({_fmt_bytes(x.get('shuffle_file_bytes', 0))}) "
                f"spilled={x.get('shuffle_spilled_rows', 0)} rows "
                f"({_fmt_bytes(x.get('shuffle_spilled_bytes', 0))})")
        if any(x.get(k) for k in ("shuffle_device_bytes",
                                  "shuffle_host_bytes",
                                  "shuffle_device_fallbacks")):
            lines.append(
                f"shuffle: device={_fmt_bytes(x.get('shuffle_device_bytes', 0))} "
                f"({x.get('shuffle_device_collectives', 0)} collectives, "
                f"{x.get('shuffle_device_exchanges', 0)} exchanges, "
                f"{x.get('shuffle_device_rows', 0)} rows) "
                f"host={_fmt_bytes(x.get('shuffle_host_bytes', 0))} "
                f"fallbacks={x.get('shuffle_device_fallbacks', 0)}")
            if x.get("shuffle_device_overlap_exchanges") \
                    or x.get("shuffle_barrier_idle_ns"):
                lines.append(
                    f"  overlap: exchanges="
                    f"{x.get('shuffle_device_overlap_exchanges', 0)} "
                    f"barrier_idle="
                    f"{_fmt_ns(x.get('shuffle_barrier_idle_ns', 0))}")
        saved_w = x.get("worker_frame_compressed_bytes_saved", 0)
        saved_r = x.get("rss_put_compressed_bytes_saved", 0)
        if saved_w or saved_r:
            lines.append(
                f"frame compression: worker={_fmt_bytes(saved_w)} saved "
                f"rss_put={_fmt_bytes(saved_r)} saved")
        if any(x.get(k) for k in ("stage_loop_tasks",
                                  "stage_loop_fallbacks")):
            lines.append(
                f"stage loop: tasks={x.get('stage_loop_tasks', 0)} "
                f"programs={x.get('stage_loop_calls', 0)} "
                f"batches={x.get('stage_loop_batches', 0)} "
                f"rows={x.get('stage_loop_rows', 0)} "
                f"lanes={x.get('stage_loop_lanes', 0)} "
                f"decimal={x.get('stage_loop_decimal_rows', 0)} "
                f"dispatches_avoided="
                f"{x.get('stage_loop_staged_dispatches_avoided', 0)} "
                f"reserves={x.get('stage_loop_reserves', 0)} "
                f"probe_rounds={x.get('stage_loop_full_rounds', 0)}"
                f"+{x.get('stage_loop_narrow_rounds', 0)}narrow "
                f"regrows={x.get('stage_loop_regrows', 0)} "
                f"undone={x.get('stage_loop_undone_steps', 0)} "
                f"fallbacks={x.get('stage_loop_fallbacks', 0)}")
        if x.get("window_rows"):
            lines.append(
                f"window={x.get('window_resident_rows', 0)}"
                f"/{x.get('window_rows', 0)} rows resident "
                f"runs={x.get('window_partitions', 0)} "
                f"scan={_fmt_bytes(x.get('window_scan_bytes', 0))}")
        if (x.get("dict_rows_coded") or x.get("dict_rows_decoded")
                or x.get("expand_rows_out")):
            lines.append(
                f"dict: coded={x.get('dict_rows_coded', 0)} "
                f"decoded={x.get('dict_rows_decoded', 0)} "
                f"unified={x.get('dict_unified', 0)} "
                f"remap_rows={x.get('dict_remap_rows', 0)} "
                f"expand_rows_out={x.get('expand_rows_out', 0)}")
        if x.get("stream_epochs"):
            epochs = x.get("stream_epochs", 0)
            wall = x.get("stream_epoch_wall_ns", 0)
            lines.append(
                f"stream: epochs={epochs} "
                f"epoch_wall={_fmt_ns(wall // max(1, epochs))}/avg "
                f"rows={x.get('stream_rows', 0)} "
                f"records={x.get('stream_records', 0)} "
                f"late={x.get('stream_late_records', 0)} "
                f"watermark_delay={x.get('stream_watermark_delay_ms_last', 0)}ms "
                f"state={_fmt_bytes(x.get('stream_window_state_bytes_last', 0))} "
                f"lag={x.get('stream_source_lag_records_last', 0)} "
                f"ckpts={x.get('stream_checkpoints', 0)} "
                f"recoveries={x.get('stream_recoveries', 0)} "
                f"sink_commits={x.get('stream_sink_commits', 0)} "
                f"dup_skips={x.get('stream_sink_dup_skips', 0)}")
        bn_line = format_bottleneck_footer(self.bottleneck)
        if bn_line is not None:
            lines.append(bn_line)
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.render_text()


def _run_execution_plan(plan, keep_result: bool) -> tuple:
    """Run every partition of an in-process ExecutionPlan through the
    task runtime; returns (merged tree, partitions, rows, table|None)."""
    import pyarrow as pa

    from blaze_tpu.bridge.runtime import NativeExecutionRuntime

    n = plan.num_partitions
    merged = MetricNode()
    rows = 0
    batches = []
    for p in range(n):
        rt = NativeExecutionRuntime(
            {"stage_id": 0, "partition_id": p, "num_partitions": n},
            plan=plan)
        # snapshot BEFORE start(): the producer thread begins pulling
        # batches immediately, and the fused tree may be shared across
        # partition runtimes (counters accumulate on the same nodes)
        before = rt.plan.collect_metrics()
        rt.start()
        try:
            for rb in rt.batches():
                rows += rb.num_rows
                if keep_result:
                    batches.append(rb)
        finally:
            after = rt.finalize()
        merged.merge_from(after.diff(before))
    table = None
    if keep_result:
        table = (pa.Table.from_batches(batches) if batches
                 else pa.Table.from_batches([], schema=plan.schema.to_arrow()))
    return merged, n, rows, table


_READER_NODES = ("IpcReaderExec", "FFIReaderExec")


def _stitch_stages(tree: MetricNode, deps: List[int], sched) -> MetricNode:
    """Reconnect producer-stage metric trees under the reader nodes that
    consumed them, recreating the full pre-split operator tree.  Reader
    nodes appear in the result tree in the same DFS order the splitter
    discovered the exchanges (Stage.deps order)."""
    pending = list(deps)

    def walk(node: MetricNode) -> None:
        # snapshot: the appended subtree was stitched recursively with its
        # OWN stage's deps — walking into it would consume this level's
        children = list(node.children)
        if node.name in _READER_NODES and pending:
            sid = pending.pop(0)
            sub = sched.stage_metrics.get(sid)
            if sub is not None and sid < len(sched.stages):
                node.children.append(
                    _stitch_stages(sub, sched.stages[sid].deps, sched))
        for c in children:
            walk(c)

    walk(tree)
    return tree


def _run_plan_dict(plan: Dict[str, Any],
                   work_dir: Optional[str]) -> tuple:
    """Run an engine-IR dict through the stage DAG scheduler."""
    from blaze_tpu.plan.stages import DagScheduler

    sched = DagScheduler(work_dir=work_dir)
    table = sched.run_collect(plan)
    tree = sched.collect_metrics() or MetricNode()
    if sched.exec_mode == "staged" and sched.stages:
        tree = _stitch_stages(tree, sched.stages[-1].deps, sched)
    if sched.exec_mode == "staged" and sched.stages:
        partitions = sched.stages[-1].num_tasks
    else:
        partitions = 1
    return (tree, partitions, table.num_rows, sched.exec_mode or "local",
            table)


def explain_analyze(plan: Union[Dict[str, Any], Any], *,
                    query_id: Optional[str] = None,
                    work_dir: Optional[str] = None,
                    record: bool = True,
                    keep_result: bool = False) -> QueryProfile:
    """Execute `plan` (an ExecutionPlan instance or an engine-IR dict)
    and return the merged query profile.

    `print(explain_analyze(plan))` renders the annotated operator tree;
    `.to_dict()` is the JSON served on /profile/<qid> when `record`.
    With `keep_result` the output table rides along on `.result` (for
    harnesses that profile AND verify rows in one run)."""
    from blaze_tpu.bridge import profiling, tracing, ui, xla_stats
    from blaze_tpu.bridge.placement import host_resident
    from blaze_tpu.ops.base import ExecutionPlan

    qid = query_id or ui.next_query_id()
    xla_before = xla_stats.snapshot()
    t0 = time.perf_counter_ns()
    with tracing.execution_context(query=qid), \
            tracing.span("explain_analyze", query=qid):
        if isinstance(plan, ExecutionPlan):
            tree, partitions, rows, table = _run_execution_plan(
                plan, keep_result)
            mode = "local"
        else:
            tree, partitions, rows, mode, table = _run_plan_dict(
                plan, work_dir)
    wall_ns = time.perf_counter_ns() - t0

    bottleneck = None
    spans = tracing.spans_for_query(qid)
    if spans:
        from blaze_tpu.bridge import critical_path
        bottleneck = critical_path.bottleneck_report(spans, wall_ns / 1e9)

    profile = QueryProfile(
        query_id=qid, wall_ns=wall_ns, tree=tree, partitions=partitions,
        exec_mode=mode, xla=xla_stats.delta(xla_before),
        kernels=xla_stats.compile_report()["kernels"],
        programs=xla_stats.program_load_summary(
            since_ns=t0, until_ns=t0 + wall_ns, top=5)["top"],
        placement="host" if host_resident() else "device",
        output_rows=rows, bottleneck=bottleneck,
        result=table if keep_result else None)
    if record:
        profiling.record_profile(qid, profile.to_dict())
        ui.record_completion(qid, wall_ns / 1e9, metrics=tree.to_dict())
    return profile
