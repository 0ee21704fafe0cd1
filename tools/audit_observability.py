#!/usr/bin/env python3
"""ROADMAP D8's audit: which counters and span names does nothing read?

  JAX_PLATFORMS=cpu python3 tools/audit_observability.py [--markdown]

Lists every key of `xla_stats.snapshot()` and every name of
`tracing.SPAN_NAMES` that none of these mentions by name:

  metric    a layer-metric file (`benchmark/layer_metrics/`, and the ones
            that wait in `layer_metrics_pending/`) or a source's code and
            tables (`benchmark/sources/`)
  footer    `blaze_tpu/plan/explain.py` (the explain footers)
  entry     `benchmark/entries/*.py` (an entry's `problem()`)
  endpoint  the code behind the documented endpoints (`bridge/profiling.py`
            timeline, `bridge/critical_path.py`, `bridge/history.py`
            device ledger, `plan/advisor.py`); the blanket surfaces do
            not count (`snapshot()`, `counter_families()`, the Prometheus
            text and the history rollup carry every key whoever reads it)

A mention in `docs/performance.md` or `docs/observability.md` is NOT a
reader: the docs list whole counter families and every span name by
contract.  Of the unread names, those the docs' prose names (outside the
"Span vocabulary" table) are marked `(docs)`: somebody wrote down what
the name means, and whether an operator's use stands behind it is for
the `simplicity` issue to judge.  It deletes nothing.
"""

from __future__ import annotations

import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _text(*patterns) -> str:
    out = []
    for pattern in patterns:
        for path in sorted(glob.glob(os.path.join(ROOT, pattern))):
            with open(path) as f:
                out.append(f.read())
    return "\n".join(out)


def _docs_less_vocabulary() -> str:
    text = _text("docs/observability.md")
    start = text.index("### Span vocabulary")
    end = text.index("\n## ", start)
    return text[:start] + text[end:] + _text("docs/performance.md")


def readers() -> dict:
    return {
        "metric": _text("benchmark/layer_metrics/*.json",
                        "benchmark/layer_metrics_pending/*.json",
                        "benchmark/sources/*.py", "benchmark/sources/*.json"),
        "footer": _text("blaze_tpu/plan/explain.py"),
        "entry": _text("benchmark/entries/*.py"),
        "endpoint": _text("blaze_tpu/bridge/profiling.py",
                          "blaze_tpu/bridge/critical_path.py",
                          "blaze_tpu/bridge/history.py",
                          "blaze_tpu/plan/advisor.py"),
    }


def unread(names, texts: dict) -> list:
    out = []
    for name in names:
        stem = name[:-1] if name.endswith("*") else name
        pat = re.compile(r"(?<![A-Za-z0-9_])" + re.escape(stem)
                         + ("" if name.endswith("*") else r"(?![A-Za-z0-9_])"))
        if not any(pat.search(t) for t in texts.values()):
            out.append(name)
    return out


def audit() -> dict:
    import blaze_tpu  # noqa: F401
    from blaze_tpu.bridge import tracing, xla_stats
    texts = readers()
    docs = {"docs": _docs_less_vocabulary()}
    counters = [k for k in xla_stats.snapshot()
                if not re.match(r"chip\d+_", k)]
    out = {"counters": len(counters), "spans": len(tracing.SPAN_NAMES),
           "unread_counters": unread(counters, texts),
           "unread_spans": unread(list(tracing.SPAN_NAMES), texts)}
    for key in ("counters", "spans"):
        names = out["unread_" + key]
        out["docs_only_" + key] = [n for n in names
                                   if n not in unread(names, docs)]
    return out


def main(argv) -> int:
    got = audit()
    if "--markdown" in argv:
        print("| kind | how many | that nothing reads |\n|---|---|---|")
        for kind, key in (("`xla_stats.snapshot()` keys", "counters"),
                          ("`SPAN_NAMES`", "spans")):
            names = got["unread_" + key]
            docs = set(got["docs_only_" + key])
            print(f"| {kind} | {got[key]} | {len(names)} "
                  f"({len(docs)} of them named in the docs' prose): "
                  + ", ".join(f"`{n}`" + (" (docs)" if n in docs else "")
                              for n in names) + " |")
    else:
        for key in ("counters", "spans"):
            names = got["unread_" + key]
            docs = set(got["docs_only_" + key])
            print(f"{key}: {got[key]}, of which nothing reads {len(names)} "
                  f"({len(docs)} of them named in the docs' prose)")
            for n in names:
                print("  " + n + ("  (docs)" if n in docs else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
