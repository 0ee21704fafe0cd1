"""Split the fold's device time of a traced benchmark run by the stage
whose `stage_loop_chunk` span was open on the host while the program ran
(map side against reduce side; PERF.md section 6, PR 27).  Reads what
`benchmark/run.py --trace 1` leaves in
`.bench_work/<cell>.trace/trace_events.json`.

usage: python tools/fold_split.py <trace_events.json>..."""
import json
import sys
from collections import defaultdict

for path in sys.argv[1:]:
    d = json.load(open(path))
    ev = d["events"]
    ann = [a for a in ev["annotations"] if a[0] == "bench_query"]
    offset = d["query_starts_ns"][0] - ann[0][1]      # profiler clock -> host clock
    queries = len(ann)
    chunks = [s for s in d["spans"] if s["name"] == "stage_loop_chunk"]
    tasks = defaultdict(set)
    for s in d["spans"]:
        if s["name"] == "task":
            tasks[s["ctx"].get("stage")].add(s["ctx"].get("partition"))
    by_stage = defaultdict(float); n_by = defaultdict(int)
    for dev in ev["devices"].values():
        for name, t0, dur in dev["programs"]:
            if not name.startswith("jit_fold_impl"):
                continue
            mid = t0 + dur / 2 + offset
            stages = {c["ctx"].get("stage") for c in chunks if c["t0_ns"] <= mid <= c["t1_ns"]}
            key = stages.pop() if len(stages) == 1 else ("none" if not stages else "several")
            by_stage[key] += dur / 1e9; n_by[key] += 1
    pt = sum(dur for dev in ev["devices"].values() for name, t0, dur in dev["programs"] if "passthrough" in name) / 1e9
    print(path.split("/")[-1], "queries", queries)
    for k in sorted(by_stage, key=str):
        print(f"  stage {k}: fold {by_stage[k] / queries:.4f} s a query in {n_by[k] / queries:.1f} calls; tasks {len(tasks.get(k, ()))}")
    print(f"  pass-through program {pt / queries:.4f} s a query")
