#!/usr/bin/env python3
"""The benchmark's one command.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip per cell today.  It finds the cell in BENCHMARK.json,
makes the cell's tables (values from the configuration's `data_seed`, row
order from --seed), writes them as parquet, builds the
plan, computes the oracle's answer and what the query's aggregations have
to fold (`fold_work`: both from the tables alone, neither counted in
`setup_s`), warms the plan up until a pass neither
compiles nor loads a program, then measures a closed loop of one client for
--seconds.  `correct` is decided after the window, on every answer the
window produced.  The last line of stdout is the result; its last key,
`compared`, and the last lines of stderr hold every number compared beside
its limit.

--trace 0 reports the cell's end-to-end metrics.  --trace 1 measures a
shorter window (the traffic file's `trace_seconds`, or one query if that is
longer) under `jax.profiler` and the program's span tracer, and reports the
cell's per-layer metrics.

Exit codes: 0 a result line was printed; 2 no TPU, too few chips, or a
device kind that is not in peaks.json; 3 the checkout has no program in it;
1 anything else.  Only 0 prints a result.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
from contextlib import nullcontext  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.manifest import Cell, load_json  # noqa: E402
from benchmark.window import run_window  # noqa: E402

WARM_PASSES_MAX = 3
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def say(*parts) -> None:
    print(*parts, flush=True)


class NotCorrect(Exception):
    """The run cannot be timed: a warm-up answer differs, or the plan took a
    path the cell is not there to time."""


class ProgramEvents:
    """Programs JAX asked its backend for (compiled, or loaded from the
    persistent cache), and how many of them the cache answered.  Covers the
    eager glue programs that the program's own `meter_jit` never sees."""

    def __init__(self):
        import jax
        self.requested = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.requested += 1

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def device_gate(chips: int, peaks: dict):
    """(devices, peak entry), or exit 2 before any data is made."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.stderr.write(
            f"benchmark: no accelerator: jax.devices()[0] is {d0.platform!r} "
            f"({d0.device_kind}); this benchmark measures on a TPU only\n")
        raise SystemExit(2)
    if len(devices) < chips:
        sys.stderr.write(f"benchmark: the cell needs {chips} chips, JAX "
                         f"sees {len(devices)}\n")
        raise SystemExit(2)
    if d0.device_kind not in peaks["devices"]:
        sys.stderr.write(f"benchmark: device kind {d0.device_kind!r} is not "
                         f"in peaks.json; a peak is never guessed\n")
        raise SystemExit(2)
    return devices, peaks["devices"][d0.device_kind]


class Run:
    """One run of one cell: set-up, window, verdict."""

    def __init__(self, cell, seed: int, work_dir: str, platform: str):
        self.cell, self.seed, self.work_dir = cell, seed, work_dir
        self.platform = platform  # where placement has to come out
        self.parts = {}           # set-up, by part, seconds
        self.results = []         # answers the window produced

    # ---- set-up ----------------------------------------------------------
    def make_data(self) -> None:
        cfg = self.cell.config
        t0 = time.perf_counter()
        gen = self.cell.module("data", cfg["generator"])
        self.query = self.cell.module("queries", self.cell.traffic["query"])
        self.tables = gen.make_tables(self.query.TABLES, cfg["scale"],
                                      cfg["data_seed"], cfg["splits"],
                                      self.seed)
        for n, t in self.tables.items():
            want = cfg["tables"].get(n)
            if want is not None and t.num_rows != want:
                raise RuntimeError(f"table {n}: {t.num_rows} rows, the "
                                   f"configuration states {want}")
        self.parts["gen_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.paths = gen.write_parquet_splits(
            self.tables, os.path.join(self.work_dir, "tables"),
            cfg["splits"], cfg["row_group_rows"])
        # read every input once so the page cache holds it
        for groups in self.paths.values():
            for g in groups:
                for p in g:
                    with open(p, "rb") as f:
                        while f.read(1 << 24):
                            pass
        self.parts["write_s"] = time.perf_counter() - t0
        self.fact_rows = self.tables[self.query.FACT].num_rows

    def make_oracle(self) -> None:
        """The oracle's answer, and what the query's aggregations have to
        fold: both from the tables alone, on the host, off `setup_s`."""
        t0 = time.perf_counter()
        self.want = self.query.oracle(self.tables)
        self.oracle_wall_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        fold_work = getattr(self.query, "fold_work", None)
        self.fold_work = fold_work(self.tables) if fold_work else None
        self.fold_work_s = time.perf_counter() - t0

    def load_program(self) -> None:
        """Native libraries, the program's imports, placement."""
        t0 = time.perf_counter()
        from blaze_tpu.bridge import native
        try:
            native.build_native_libs()
        except native.NativeBuildError as e:
            raise RuntimeError(f"native libraries did not build: {e}")
        self.parts["native_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        from blaze_tpu import config
        from blaze_tpu.bridge.placement import ensure_placement
        for key, value in self.cell.config["program_settings"].items():
            config.conf.set(key, value)
        pi = ensure_placement()
        if pi.device_kind != self.platform:
            raise NotCorrect(f"placement came out {pi.device_kind!r}, not "
                             f"{self.platform} (dispatch RTT "
                             f"{pi.rtt_ms:.2f} ms)")
        entry_mod = self.cell.module("entries", self.cell.traffic["entry"])
        self.entry = entry_mod.Entry(self.query, self.paths, self.tables,
                                     self.cell.config, self.work_dir)
        self.parts["load_s"] = time.perf_counter() - t0

    def one_query(self, annotate=None):
        """(answer, wall, perf_counter_ns at the start).  Only `entry.run()`
        is on the clock; `annotate` puts it inside a profiler annotation."""
        self.entry.begin()
        try:
            with annotate("bench_query") if annotate else nullcontext():
                start_ns = time.perf_counter_ns()
                t0 = time.perf_counter()
                got = self.entry.run()
                wall = time.perf_counter() - t0
        finally:
            self.entry.end()
        return got, wall, start_ns

    def judge(self, got):
        nums = check.compare(got, self.want, self.query.KEYS,
                             self.query.ORDERED)
        ok, line = check.verdict(nums)
        return ok, line, nums

    def warm_up(self, events: ProgramEvents) -> float:
        """Repeat the query until a pass asks the backend for no program;
        every pass's answer has to hold.  Returns the last pass's wall."""
        t0 = time.perf_counter()
        wall = None
        for i in range(WARM_PASSES_MAX):
            before = events.requested
            got, wall, _ = self.one_query()
            asked = events.requested - before
            ok, line, _ = self.judge(got)
            say(f"warm-up {i + 1}: wall {wall:.3f}s, programs compiled or "
                f"loaded {asked}; {line}")
            if not ok:
                raise NotCorrect(f"warm-up answer {i + 1} differs from the "
                                 f"oracle: {line}")
            why = self.entry.problem()
            if why:
                raise NotCorrect(why)
            del got
            if asked == 0:
                break
        else:
            raise NotCorrect(f"still compiling or loading programs after "
                             f"{WARM_PASSES_MAX} warm-up passes")
        gc.collect()
        self.parts["warm_s"] = time.perf_counter() - t0
        return wall

    # ---- the window -------------------------------------------------------
    def window(self, seconds: float, expected_s: float, annotate=None,
               after=None):
        """(walls, perf_counter_ns at which each query started).  `annotate`
        wraps each query in a profiler annotation; `after` runs between
        queries, off every query's clock."""
        starts = []

        def run_one() -> float:
            got, wall, start_ns = self.one_query(annotate)
            starts.append(start_ns)
            self.results.append(got)
            if after is not None:
                after()
            gc.collect()
            return wall

        return run_window(run_one, seconds, expected_s), starts

    def verdicts(self):
        """Compares every answer of the window; returns how many differ
        and the worst reading of each number compared."""
        failed = 0
        worst = {}
        for got in self.results:
            ok, _line, nums = self.judge(got)
            failed += not ok
            for k, v in nums.items():
                worst[k] = max(worst.get(k, 0), v)
        _ok, line = check.verdict(worst)
        say(f"answers compared {len(self.results)}, differing {failed}; "
            f"worst of each number: {line}")
        return failed, worst


def traced_window(run: Run, seconds: float, expected_s: float, trace_dir: str):
    """The window under the profiler and the span tracer.  Returns what the
    per-layer sources read."""
    import jax
    from blaze_tpu.bridge import profiling, tracing, xla_stats
    from benchmark.sources import device_trace
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the Python tracer slows the host
    options.host_tracer_level = 1     # TraceAnnotation only
    old = profiling.recent_metrics()  # held, so that no id is used again
    seen = {id(t) for t in old}
    trees = []

    def new_trees():
        # the program keeps only its newest trees: collect after each query
        for t in profiling.recent_metrics():
            if id(t) not in seen:
                seen.add(id(t))
                trees.append(t)

    before = xla_stats.snapshot()
    tracing.start_tracing()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        walls, starts = run.window(seconds, expected_s,
                                   annotate=jax.profiler.TraceAnnotation,
                                   after=new_trees)
    finally:
        jax.profiler.stop_trace()
        spans = tracing.stop_tracing()
    counters = xla_stats.delta(before)
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    events = device_trace.extract(found[0])
    summary = device_trace.reduce(events, spans, starts)
    # what `reduce` was given, kept beside the trace: the recorded trace
    # under tests/data is a cut of one of these
    with open(os.path.join(trace_dir, "trace_events.json"), "w") as f:
        json.dump({"events": events, "query_starts_ns": starts,
                   "spans": [s for s in spans if s["dur_ns"] > 0
                             and not s["name"].startswith("operator:")]}, f)
    return walls, {"counters": counters, "trees": trees, "spans": spans,
                   "trace": summary}


def drive(cell, seed: int, seconds: float, trace: int, devices, peaks: dict,
          t_process: float) -> dict:
    """Everything after the look for a chip: returns the result line's
    object.  `devices` are the chips the gate found; placement has to come
    out on their platform."""
    import blaze_tpu
    d0 = devices[0]
    events = ProgramEvents()
    say(f"cell {cell.name}: config {cell.entry['config']}, traffic "
        f"{cell.entry['traffic']}, seed {seed}, {seconds:g}s, trace {trace}; "
        f"{len(devices)} x {d0.device_kind}; compile cache "
        f"{blaze_tpu.COMPILE_CACHE_DIR}")
    work_dir = os.path.join(cell.root, ".bench_work", cell.name)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    run = Run(cell, seed, work_dir, d0.platform)
    # process start to here: the interpreter, the imports of jax, pyarrow
    # and the program, and the runtime's first contact with the chip
    run.parts["import_s"] = time.perf_counter() - t_process
    try:
        run.make_data()
        run.make_oracle()
        try:
            run.load_program()
            expected_s = run.warm_up(events)
        except NotCorrect as e:
            say(f"NOT CORRECT before the window: {e}")
            expected_s = None
        loaded_in_setup = events.cache_hits
        setup_s = time.perf_counter() - t_process - run.oracle_wall_s \
            - run.fold_work_s
        say(json.dumps({"setup_parts": dict(
            run.parts, oracle_wall_s=run.oracle_wall_s,
            fold_work_s=run.fold_work_s, setup_s=setup_s,
            programs_requested=events.requested,
            programs_loaded=loaded_in_setup)}))

        walls, read_ctx, failed, worst = [], {}, 1, {}
        compiles_in_window = None
        if expected_s is not None:
            asked_before = events.requested
            if trace:
                walls, read_ctx = traced_window(
                    run, min(seconds, float(cell.traffic["trace_seconds"])),
                    expected_s, work_dir + ".trace")
            else:
                walls, _ = run.window(seconds, expected_s)
            compiles_in_window = events.requested - asked_before
            say("query walls: " + " ".join(f"{w:.4f}" for w in walls))
            failed, worst = run.verdicts()
            say(f"programs compiled or loaded inside the window: "
                f"{compiles_in_window} (limit 0)")
        correct = bool(walls) and failed == 0 and compiles_in_window == 0

        # a backend that keeps no memory statistics (the CPU, in a
        # rehearsal) reads 0
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devices[:cell.chips])
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak_bytes}
        result = {"correct": correct, "attempted": max(len(walls), 1),
                  "failed": failed}
        if not trace:
            measured = {"setup_s": setup_s}
            if walls:
                measured["query_wall_s"] = statistics.median(walls)
            result["metrics"] = {
                m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end() if m["name"] in measured}
        else:
            ctx = dict(read_ctx, queries=len(walls), query=run.query,
                       fact_rows=run.fact_rows, peaks=peaks,
                       fold_work=run.fold_work,
                       harness=dict(
                           run.parts,  # set-up by part: `setup_*_s`
                           query_wall_max_s=max(walls, default=None),
                           oracle_wall_s=run.oracle_wall_s,
                           programs_loaded=loaded_in_setup,
                           compiles_in_window=compiles_in_window,
                           peak_hbm_mb=peak_bytes / 1e6))
            result["metrics"] = {}
            for entry, spec in cell.layer_metrics() if walls else []:
                value = cell.module("sources", spec["source"]).read(spec, ctx)
                if value is not None:
                    result["metrics"][entry["name"]] = {
                        "value": value, "unit": entry["unit"]}
            summary = read_ctx.get("trace") or {}
            if summary:
                from benchmark.sources import device_trace
                device["busy_s"] = summary["busy_s"]
                device["window_s"] = summary["window_s"]
                result["breakdown"] = device_trace.breakdown(summary)
        result["device"] = device
        # every number compared beside its limit: the result's last key,
        # and the last lines of standard error
        compared = {name: {"value": worst.get(name), "limit": limit}
                    for name, limit in check.LIMITS.items()}
        compared["compiles_in_window"] = {"value": compiles_in_window,
                                          "limit": 0}
        result["compared"] = compared
        for name, c in compared.items():
            sys.stderr.write(f"compared {name}={c['value']!r} "
                             f"(limit {c['limit']!r})\n")
        sys.stderr.flush()
    finally:
        shutil.rmtree(os.path.join(work_dir, "tables"), ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "blaze_tpu")):
        sys.stderr.write("benchmark: this checkout holds no program "
                         "(blaze_tpu/) to measure\n")
        return 3
    # the program configures JAX (x64, the compile cache inside the
    # checkout) as it is imported, before the first device is touched
    import blaze_tpu  # noqa: F401
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))
    devices, peak = device_gate(cell.chips, peaks)
    result = drive(cell, args.seed, args.seconds, args.trace, devices, peak,
                   _T_PROCESS)
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
