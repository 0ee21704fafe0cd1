"""Fault-site conformance: every registered chaos site must be
exercised by a test, so a new site cannot land without coverage and a
renamed site cannot silently orphan its tests."""

import os
import re

from blaze_tpu import faults

_HERE = os.path.dirname(os.path.abspath(__file__))


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


def test_every_fault_site_is_exercised():
    corpus = _corpus()
    missing = []
    for site in faults.SITES:
        # word-boundary safe for hyphenated site names: "worker-slow"
        # must not match inside "worker-slow-extra" or "x-worker-slow"
        if not re.search(rf"(?<![-\w]){re.escape(site)}(?![-\w])",
                         corpus):
            missing.append(site)
    assert not missing, (
        f"fault sites with no test coverage: {missing} — add a test "
        f"exercising faults at the site (faults.scoped / "
        f"faults.configure)")


def test_sites_registry_matches_docstring():
    """The module docstring's site table is user-facing documentation;
    every registered site must appear in it."""
    doc = faults.__doc__ or ""
    undocumented = [s for s in faults.SITES if s not in doc]
    assert not undocumented, (
        f"sites missing from the faults module docstring: {undocumented}")
