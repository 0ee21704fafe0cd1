"""Span-name conformance: the tracing registry (tracing.SPAN_NAMES) is
the contract for the whole observability surface.  Every registered
name must be exercised by a test, documented in
docs/observability.md, and actually emitted somewhere in the engine —
so a new span cannot land without coverage or docs, and a renamed or
removed emitter cannot silently orphan its registry entry.  Mirrors
tests/test_fault_sites.py for chaos sites."""

import os
import re

from blaze_tpu.bridge import tracing

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
_PKG = os.path.join(_REPO, "blaze_tpu")

# tracing.span / instant / emit_span call with a literal (or f-string)
# name as the first argument, possibly wrapped to the next line
_EMIT_RE = re.compile(
    r"(?:span|instant|emit_span)\(\s*f?\"([^\"\n]+)\"")


def _corpus() -> str:
    chunks = []
    for name in sorted(os.listdir(_HERE)):
        if not (name.startswith("test_") and name.endswith(".py")):
            continue
        if name == os.path.basename(__file__):
            continue  # self-references must not count as coverage
        with open(os.path.join(_HERE, name)) as f:
            chunks.append(f.read())
    return "\n".join(chunks)


def _emitted_names() -> set:
    """Every span name the engine can emit, harvested from source.
    f-string names collapse to their literal prefix + '*' so dynamic
    families (operator:<name>) map onto their wildcard registration."""
    names = set()
    for root, _dirs, files in os.walk(_PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                src = f.read()
            for m in _EMIT_RE.finditer(src):
                name = m.group(1)
                if "{" in name:
                    name = name.split("{", 1)[0] + "*"
                names.add(name)
    return names


def test_every_span_name_is_exercised():
    corpus = _corpus()
    missing = []
    for name in tracing.SPAN_NAMES:
        if name.endswith("*"):
            # dynamic family: any member with the literal prefix counts
            ok = name[:-1] in corpus
        else:
            # word-boundary safe for snake_case names: "task" must not
            # match inside "task_attempt" or "worker_task"
            ok = re.search(rf"(?<![-\w]){re.escape(name)}(?![-\w])",
                           corpus)
        if not ok:
            missing.append(name)
    assert not missing, (
        f"span names with no test coverage: {missing} — add a "
        f"test that emits or asserts on the span (see tests/"
        f"test_tracing.py)")


def test_every_span_name_is_documented():
    with open(os.path.join(_REPO, "docs", "observability.md")) as f:
        doc = f.read()
    undocumented = [n for n in tracing.SPAN_NAMES if n not in doc]
    assert not undocumented, (
        f"span names missing from docs/observability.md: {undocumented}")
    assert all(d.strip() for d in tracing.SPAN_NAMES.values()), \
        "every registry entry needs a one-line doc naming its emitter"


def test_no_dead_or_unregistered_span_names():
    emitted = _emitted_names()
    unregistered = sorted(n for n in emitted if n not in tracing.SPAN_NAMES)
    assert not unregistered, (
        f"emitted but not registered (tracing raises at runtime when "
        f"enabled): {unregistered}")
    dead = sorted(n for n in tracing.SPAN_NAMES if n not in emitted)
    assert not dead, (
        f"registered but never emitted anywhere in blaze_tpu/: {dead} — "
        f"remove the registry entry or wire up the emitter")
