"""End-to-end trace propagation: client -> coordinator -> scheduler ->
agent -> back, one trace_id, spans stitched across the REST control plane.

The worker agent records its executor spans into a PRIVATE tracer
(runtime/agent.py) and ships them to the coordinator over
``POST /trace_spans/<wid>`` — so when these assertions find agent-side
span names in the coordinator's ``/trace/<job_id>`` response, the REST
shipping path genuinely ran: the coordinator's process-global tracer never
saw those spans directly, even with the agent threads living in this test
process."""

import threading
import time

import pytest
import requests
from sklearn.linear_model import LogisticRegression
from sklearn.model_selection import GridSearchCV

from cs230_distributed_machine_learning_tpu import MLTaskManager
from cs230_distributed_machine_learning_tpu.obs import TRACER
from cs230_distributed_machine_learning_tpu.runtime.agent import WorkerAgent
from cs230_distributed_machine_learning_tpu.runtime.cluster import ClusterRuntime
from cs230_distributed_machine_learning_tpu.runtime.coordinator import Coordinator
from cs230_distributed_machine_learning_tpu.runtime.server import create_app
from cs230_distributed_machine_learning_tpu.utils.config import get_config


@pytest.fixture()
def http_coordinator():
    from werkzeug.serving import make_server

    get_config().scheduler.heartbeat_interval_s = 0.1
    cluster = ClusterRuntime()
    coord = Coordinator(cluster=cluster)
    app = create_app(coord)
    server = make_server("127.0.0.1", 0, app, threaded=True)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_port}"
    yield coord, url
    server.shutdown()
    cluster.shutdown()


def test_trace_stitches_across_agent_round_trip(http_coordinator):
    coord, url = http_coordinator
    agent = WorkerAgent(url, poll_timeout_s=0.5, register_backoff_s=0.1)
    agent.start()
    try:
        m = MLTaskManager(url=url)
        status = m.train(
            GridSearchCV(LogisticRegression(max_iter=300), {"C": [0.1, 1.0]}, cv=3),
            "iris",
            show_progress=False,
            timeout=120,
        )
        assert status["job_status"] == "completed"
        assert m.trace_id is not None

        # span shipping is asynchronous relative to job completion (the
        # agent posts after its batch, the job thread records its closing
        # spans after finalize): poll until the full chain is present —
        # submit -> expand -> place -> execute (agent-side batch + phases)
        # -> aggregate
        required = {
            "http.train",
            "job.submit",
            "job.expand",
            "schedule.place",
            "job.execute",
            "agent.poll",
            "executor.batch",
            "executor.compile",
            "executor.dispatch",
            "executor.fetch",
            "job.aggregate",
        }
        deadline = time.time() + 10
        names = set()
        while time.time() < deadline:
            body = requests.get(f"{url}/trace/{m.job_id}", timeout=10).json()
            names = {s["name"] for s in body["spans"]}
            if required <= names:
                break
            time.sleep(0.2)
        assert required <= names, f"missing {sorted(required - names)}"

        # ONE consistent trace id, minted by the client
        assert body["trace_id"] == m.trace_id
        assert all(s["trace_id"] == m.trace_id for s in body["spans"])

        # the agent-side spans were NOT recorded by the coordinator's
        # global tracer — they arrived via POST /trace_spans
        local_names = {
            s["name"] for s in TRACER.spans_for(m.trace_id)
        }
        assert "executor.batch" in local_names  # ingested
        shipped = [
            s for s in body["spans"] if s["name"] == "executor.batch"
        ]
        assert shipped, "agent batch span missing"

        # tree shape: the executor batch nests its phases (real spans)
        def find(nodes, name):
            for n in nodes:
                if n["name"] == name:
                    return n
                hit = find(n["children"], name)
                if hit is not None:
                    return hit
            return None

        batch = find(body["tree"], "executor.batch")
        assert batch is not None
        child_names = {c["name"] for c in batch["children"]}
        assert {"executor.compile", "executor.dispatch", "executor.fetch"} <= child_names

        # cluster counters moved by the same placed-and-executed job:
        # dispatched/polls/acks and the placement-latency histogram
        text = requests.get(f"{url}/metrics/prom", timeout=10).text

        def sample(name):
            import re

            hit = re.search(rf"^{name}(?:\{{[^}}]*\}})? (\S+)$", text, re.M)
            assert hit, f"{name} missing from exposition"
            return float(hit.group(1))

        assert sample("tpuml_subtasks_dispatched_total") >= 2  # two trials
        assert sample("tpuml_agent_polls_total") >= 1
        assert sample("tpuml_agent_acks_total") >= 2
        assert sample("tpuml_scheduler_placement_seconds_count") >= 2
        assert sample("tpuml_workers_alive") >= 1

        # unknown job -> 404
        assert (
            requests.get(f"{url}/trace/not-a-job", timeout=10).status_code == 404
        )
    finally:
        agent.stop()


@pytest.fixture()
def http_fleet(http_coordinator):
    """The two-process topology: a stateless front end (its own HTTP
    server) relaying to the coordinator shard — the hop that used to be
    the tracing blind spot."""
    from werkzeug.serving import make_server

    from cs230_distributed_machine_learning_tpu.runtime.frontend import (
        create_frontend_app,
    )

    coord, url = http_coordinator
    fe_app = create_frontend_app([url])
    fe_server = make_server("127.0.0.1", 0, fe_app, threaded=True)
    fe_thread = threading.Thread(target=fe_server.serve_forever, daemon=True)
    fe_thread.start()
    fe_url = f"http://127.0.0.1:{fe_server.server_port}"
    yield coord, url, fe_url
    fe_server.shutdown()


def _find(nodes, name):
    for n in nodes:
        if n["name"] == name:
            return n
        hit = _find(n["children"], name)
        if hit is not None:
            return hit
    return None


def test_frontend_proxy_span_roots_the_stitched_trace(http_fleet):
    """A job submitted THROUGH the front end produces one stitched trace
    whose root is ``frontend.proxy`` with the shard's ``http.train``
    nested under it: the front end forwards its open span id as
    X-Parent-Span, records the proxy span into its own tracer, and ships
    it to the owning shard's /trace_spans ingest."""
    coord, url, fe_url = http_fleet
    agent = WorkerAgent(url, poll_timeout_s=0.5, register_backoff_s=0.1)
    agent.start()
    try:
        m = MLTaskManager(url=fe_url)
        status = m.train(
            GridSearchCV(LogisticRegression(max_iter=300), {"C": [0.1]}, cv=3),
            "iris",
            show_progress=False,
            timeout=120,
        )
        assert status["job_status"] == "completed"

        # poll the stitched trace THROUGH the front end until the shipped
        # frontend.proxy span landed next to the shard-side chain
        deadline = time.time() + 10
        body, names = {}, set()
        while time.time() < deadline:
            body = requests.get(
                f"{fe_url}/trace/{m.job_id}", timeout=10
            ).json()
            names = {s["name"] for s in body.get("spans", [])}
            if {"frontend.proxy", "http.train", "executor.batch"} <= names:
                break
            time.sleep(0.2)
        assert {"frontend.proxy", "http.train", "executor.batch"} <= names, (
            f"missing {sorted({'frontend.proxy', 'http.train', 'executor.batch'} - names)}"
        )
        assert body["trace_id"] == m.trace_id

        # stitching: http.train is NOT a root — it nests under the proxy
        # span of the relayed /train request
        roots = {n["name"] for n in body["tree"]}
        assert "frontend.proxy" in roots
        assert "http.train" not in roots
        proxy = next(
            n for n in body["tree"]
            if n["name"] == "frontend.proxy"
            and _find(n["children"], "http.train") is not None
        )
        assert proxy["attrs"]["route"] == "train"
        assert proxy["attrs"]["shard"] == 0
        assert proxy["attrs"]["minted"] is False  # client sent the id
        assert proxy["process"].startswith("frontend:")

        # the trace response relayed the id end to end
        r = requests.get(
            f"{fe_url}/trace/{m.job_id}",
            headers={"X-Trace-Id": m.trace_id},
            timeout=10,
        )
        assert r.headers.get("X-Trace-Id") == m.trace_id

        # a headerless relayed request gets a MINTED trace id echoed back
        r = requests.get(f"{fe_url}/trace/{m.job_id}", timeout=10)
        minted = r.headers.get("X-Trace-Id")
        assert minted and minted != m.trace_id

        # the critical-path report is reachable through the front end and
        # starts at the proxy hop
        deadline = time.time() + 10
        cp = {}
        while time.time() < deadline:
            cp = requests.get(
                f"{fe_url}/critical_path/{m.job_id}", timeout=10
            ).json()
            if cp.get("segments") and cp["segments"][0]["name"] == "frontend.proxy":
                break
            time.sleep(0.2)
        assert cp["segments"][0]["name"] == "frontend.proxy"
        assert sum(s["duration_s"] for s in cp["segments"]) == pytest.approx(
            cp["wall_s"], rel=1e-6
        )

        # and the Perfetto export routes by the job stamp too
        exp = requests.get(
            f"{fe_url}/trace/{m.job_id}/export?format=perfetto", timeout=10
        ).json()
        assert exp["format"] == "perfetto"
        assert any(
            e.get("name") == "frontend.proxy"
            for e in exp["document"]["traceEvents"]
        )
    finally:
        agent.stop()
