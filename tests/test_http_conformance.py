"""HTTP surface conformance: every route the profiling service
declares in `profiling.ROUTES` answers with its documented status, a
correct Content-Type, and a parseable body — including the new
/stats, /progress and /query/<qid>/bottleneck endpoints — plus the
`tools.top` CLI against a live server.
"""

import json
import os
import subprocess
import time
import urllib.error
import urllib.request

import pytest

from blaze_tpu import config
from blaze_tpu.bridge import history, profiling, tracing, ui
from blaze_tpu.memory import MemManager
from blaze_tpu.plan import statstore
from blaze_tpu.serving import progress

_QID = "q-conf"
_FP = "fp-conf"

#: per-route request query string (avoids side effects: /trace/start
#: with a bogus param is rejected before any profiler state changes)
_QUERY = {"/trace/start": "?nope=1", "/serving/cancel": f"?qid={_QID}"}

#: allowed statuses; everything not listed must 200 once seeded
_EXPECT = {"/trace/start": {400},
           "/trace/stop": {200, 500}}  # 500: no active profiler trace

_CTYPE = {"/metrics.prom": "text/plain", "/auron.html": "text/html"}


@pytest.fixture(autouse=True)
def seeded_service(tmp_path):
    """A live service with every data plane populated for _QID."""
    MemManager.init(4 << 30)
    ui.reset()
    progress.reset()
    config.conf.set(config.TRACE_ENABLE.key, "on")
    config.conf.set(config.HISTORY_ENABLE.key, "true")
    config.conf.set(config.HISTORY_DIR.key, str(tmp_path / "hist"))
    config.conf.set(config.STATS_ENABLE.key, "on")
    config.conf.set(config.STATS_DIR.key, str(tmp_path / "stats"))
    for mod in (tracing, history, statstore):
        mod.reset_conf_probe()

    with tracing.execution_context(query=_QID):
        with tracing.span("task", stage=0):
            time.sleep(0.002)
    profiling.record_metrics({"name": "ConfSeedExec",
                              "values": {"output_rows": 1},
                              "children": []})
    profiling.record_profile(_QID, {"query_id": _QID, "wall_ns": 1000,
                                    "tree": None, "output_rows": 1})
    history.note_admitted(_QID, tenant="t")
    history.note_finished(_QID, status="done", tenant="t", wall_s=0.01)
    statstore.ingest({"fingerprint": _FP, "wall_s": 0.01,
                      "task_ns": [1_000_000], "counters": {},
                      "fallback_reasons": {}, "stages": []})
    progress.note_query_start(_QID, fingerprint=_FP)
    progress.note_stage_start(_QID, 0, 2)
    progress.note_task_done(_QID, 0)

    port = profiling.start_http_service()
    try:
        yield port
    finally:
        profiling.stop_http_service()
        for opt in (config.TRACE_ENABLE, config.HISTORY_ENABLE,
                    config.HISTORY_DIR, config.STATS_ENABLE,
                    config.STATS_DIR):
            config.conf.unset(opt.key)
        for mod in (tracing, history, statstore):
            mod.reset_conf_probe()
        tracing.stop_tracing()
        progress.reset()
        ui.reset()


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, r.headers.get("Content-Type"), \
                r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type"), e.read().decode()


def _concrete(route):
    return (route.replace("<qid>", _QID).replace("<fingerprint>", _FP)
            + _QUERY.get(route, ""))


@pytest.mark.parametrize("route", profiling.ROUTES)
def test_route_conformance(seeded_service, route):
    code, ctype, body = _get(seeded_service, _concrete(route))
    assert code in _EXPECT.get(route, {200}), \
        f"{route}: status {code}, body {body[:200]}"
    want_ctype = _CTYPE.get(route, "application/json")
    assert ctype and ctype.startswith(want_ctype), \
        f"{route}: Content-Type {ctype!r}"
    if want_ctype == "application/json":
        json.loads(body)  # every JSON route parses, error bodies too


def test_unknown_path_404_lists_all_routes(seeded_service):
    code, _ctype, body = _get(seeded_service, "/definitely/not/a/route")
    assert code == 404
    assert json.loads(body)["paths"] == list(profiling.ROUTES)


def test_bottleneck_endpoint_payload(seeded_service):
    code, _ctype, body = _get(seeded_service, f"/query/{_QID}/bottleneck")
    assert code == 200
    rep = json.loads(body)
    assert rep["v"] == 1
    assert rep["dominant"] in rep["categories"]
    assert rep["categories"]["host_compute"] >= 0.002  # the task span
    assert sum(rep["categories"].values()) == pytest.approx(
        rep["wall_s"], rel=0.01)


def test_stats_endpoints_round_trip(seeded_service):
    code, _c, body = _get(seeded_service, "/stats")
    assert code == 200
    assert any(s["fingerprint"] == _FP for s in json.loads(body))
    code, _c, body = _get(seeded_service, f"/stats/{_FP}")
    assert code == 200
    assert json.loads(body)["run_count"] == 1
    code, _c, body = _get(seeded_service, "/stats/nope")
    assert code == 404
    assert _FP in json.loads(body)["known"]


def test_progress_endpoints_round_trip(seeded_service):
    code, _c, body = _get(seeded_service, f"/query/{_QID}/progress")
    assert code == 200
    p = json.loads(body)
    assert p["tasks_done"] == 1 and p["tasks_total"] == 2
    code, _c, body = _get(seeded_service, "/progress")
    assert code == 200
    assert [q["query_id"] for q in json.loads(body)["running"]] == [_QID]


def test_top_cli_once_against_live_server(seeded_service):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [os.sys.executable, "-m", "blaze_tpu.tools.top", "--port",
         str(seeded_service), "--once"],
        capture_output=True, text=True, timeout=60, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert "QUERY" in out.stdout and _QID in out.stdout
    out = subprocess.run(
        [os.sys.executable, "-m", "blaze_tpu.tools.top", "--port",
         str(seeded_service), "--once", "--json"],
        capture_output=True, text=True, timeout=60, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert any(q["query_id"] == _QID
               for q in json.loads(out.stdout)["running"])


def test_top_cli_errors_cleanly_without_server():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [os.sys.executable, "-m", "blaze_tpu.tools.top", "--port", "1",
         "--once"],
        capture_output=True, text=True, timeout=60, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 1
    assert "no response" in out.stderr

