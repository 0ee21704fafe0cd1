"""The account stays true: the documents name only files that exist,
no CPU wall record lies at the root beside BENCHMARK.json, and no
engine or test comment names a file that was deleted.

The one measurement is `python3 benchmark/run.py` over the cells of
BENCHMARK.json (PERF.md, PERF_LEDGER.jsonl).  PERF.md, ROADMAP.md and
CHANGES.md are histories, they name what earlier PRs deleted, and are
left out."""

import os
import re
import subprocess

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md"] + sorted(
    os.path.join("docs", n)
    for n in os.listdir(os.path.join(_REPO, "docs")) if n.endswith(".md"))

# where a document's relative path may start: the docs name engine files
# as `plan/fused.py`, harness files as `run.py`
ROOTS = ("", "blaze_tpu", "benchmark", "tests", "docs")

# files a document may name although they are gone, each with the PR
# that deleted it (a document that tells a deletion has to name it);
# none at PR 43
DELETED: dict = {}

_TICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(
    r"(?<![\w./-])([\w./-]+\.(?:py|sh|json|md))(?::\d+)?(?![\w/-])")


def _tracked():
    """The files git would commit, or the files on disk where the
    checkout is no repository (a `git archive` copy)."""
    r = subprocess.run(["git", "ls-files"], cwd=_REPO, capture_output=True,
                       text=True)
    if r.returncode == 0 and r.stdout.strip():
        return r.stdout.split("\n")
    out = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "__pycache__")]
        out.extend(os.path.relpath(os.path.join(root, f), _REPO)
                   for f in files)
    return out


def _named_paths(text):
    """Back-ticked tokens that look like a path of this repository.  A
    span with a placeholder or a glob (`<cell>`, `*`, `{a,b}`) names a
    family of run-time files, not one file."""
    for span in _TICKED.findall(text):
        if any(c in span for c in "<>*{}$"):
            continue
        for m in _PATH.finditer(span):
            yield m.group(1)


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_only_paths_that_exist(doc):
    tracked = set(_tracked())
    basenames = {os.path.basename(p) for p in tracked}
    with open(os.path.join(_REPO, doc)) as f:
        text = f.read()
    missing = []
    for path in sorted(set(_named_paths(text))):
        rel = path[2:] if path.startswith("./") else path
        if rel in DELETED:
            continue
        if "/" not in rel:
            found = rel in basenames
        else:
            found = any(os.path.normpath(os.path.join(r, rel)) in tracked
                        for r in ROOTS)
        if not found:
            missing.append(path)
    assert not missing, (
        f"{doc} names files that do not exist: {missing} — correct the "
        f"sentence, or, where it tells of a deletion, list the file in "
        f"DELETED with the PR that deleted it")


def test_no_cpu_wall_records_at_the_root():
    tracked = _tracked()
    stale = sorted(p for p in tracked
                   if "/" not in p and re.fullmatch(r"BENCH_\w+\.json", p))
    assert not stale, (
        f"{stale}: a CPU run yields counts and correctness, never a time; "
        f"the record of speed is PERF_LEDGER.jsonl, written from chip runs")
    assert "BENCHMARK.json" in tracked


def test_engine_comments_name_no_deleted_file():
    needle = "bench" + ".py"  # spelled apart: this file is searched too
    hits = []
    for p in _tracked():
        if not p.endswith(".py") or not p.startswith(("blaze_tpu/",
                                                       "tests/")):
            continue
        with open(os.path.join(_REPO, p)) as f:
            for n, line in enumerate(f, 1):
                if re.search(rf"(?<![\w/]){re.escape(needle)}", line):
                    hits.append(f"{p}:{n}")
    assert not hits, (
        f"{hits} name the deleted second measurement system: say what the "
        f"code is for, not who used to call it")
