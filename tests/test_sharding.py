"""Sharded control plane (docs/ARCHITECTURE.md "Sharded control plane"):
routing invariants, fleet-wide admission caps, and QoS priority lanes.

The invariants pinned here are what make the front ends stateless:

- ``shard_of`` is a CONTENT hash — identical in every process, forever
  (a salted ``hash()`` would scatter a session over the fleet);
- job/worker ids carry an unambiguous ``s<k>-`` stamp that can never
  collide with client-minted uuids;
- a job submitted through ANY front end is visible, pollable, and
  streamable through EVERY front end;
- the global admission caps bound the FLEET's accepted load (per-shard
  shares sum to the configured total, not total x N);
- higher-priority sessions' subtasks drain dispatch queues first.
"""

import json
import subprocess
import sys
import threading
import time

import pytest
import requests
from sklearn.linear_model import LogisticRegression

from cs230_distributed_machine_learning_tpu.client.introspection import (
    extract_model_details,
)
from cs230_distributed_machine_learning_tpu.runtime.sharding import (
    id_shard,
    shard_of,
    shard_service_config,
    stamp_job_id,
    worker_prefix,
)
from cs230_distributed_machine_learning_tpu.utils.config import (
    FrameworkConfig,
)


# ---------------------------------------------------------------------
# id conventions
# ---------------------------------------------------------------------

def test_shard_of_stable_across_processes():
    """The routing hash must be process-independent: a front end started
    tomorrow must route yesterday's session to the same shard."""
    sids = ["abc", "7e1c9c1e-1111-2222-3333-444455556666", "s01-weird"]
    script = (
        "from cs230_distributed_machine_learning_tpu.runtime.sharding "
        "import shard_of; import json,sys; "
        "print(json.dumps([shard_of(s, 4) for s in "
        f"{sids!r}]))"
    )
    import os

    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, check=True,
        # a different hash seed is exactly the hazard shard_of must be
        # immune to (it would re-route every session after a restart)
        env={**os.environ, "PYTHONHASHSEED": "271",
             "JAX_PLATFORMS": "cpu"},
    )
    assert json.loads(out.stdout) == [shard_of(s, 4) for s in sids]


def test_shard_of_covers_all_shards():
    import uuid

    hit = {shard_of(str(uuid.uuid4()), 4) for _ in range(400)}
    assert hit == {0, 1, 2, 3}
    assert shard_of("anything", 1) == 0


def test_id_stamp_roundtrip():
    import uuid

    jid = str(uuid.uuid4())
    stamped = stamp_job_id(3, jid)
    assert stamped == f"s03-{jid}"
    assert id_shard(stamped) == 3
    # the 2-digit grammar bounds the fleet: minting outside it must fail
    # loudly at launch, not at first unroutable id
    with pytest.raises(ValueError):
        stamp_job_id(100, jid)
    with pytest.raises(ValueError):
        worker_prefix(100)
    # idempotent for the OWNING shard (canonical resubmits are no-ops)...
    assert stamp_job_id(3, stamped) == stamped
    # ...but a foreign-looking stamp on a client-minted id is wrapped, so
    # the OUTER stamp always names the shard that actually stores the job
    assert stamp_job_id(1, stamped) == f"s01-{stamped}"
    assert id_shard(stamp_job_id(1, stamped)) == 1
    # client-minted uuids can never be mistaken for stamps (uuid's first
    # dash is at position 8, the stamp's at position 3)
    assert id_shard(jid) is None
    assert id_shard(f"{worker_prefix(2)}worker-7") == 2


def test_shard_service_config_carves_global_caps():
    cfg = FrameworkConfig.load(env={})
    cfg.service.max_inflight_jobs = 10
    cfg.service.admission_queue_watermark = 1000
    cfg.service.max_inflight_jobs_per_session = 16
    per = shard_service_config(cfg, 4)
    # floor division: shares sum to AT MOST the global cap (ceil would
    # over-admit up to N-1 jobs past the configured total)
    assert per.service.max_inflight_jobs == 2  # 10 // 4
    assert 4 * per.service.max_inflight_jobs <= 10
    assert per.service.admission_queue_watermark == 250
    # per-SESSION cap untouched: a session lives entirely on one shard
    assert per.service.max_inflight_jobs_per_session == 16
    # n=1: identity (the unsharded deployment keeps its exact config)
    assert shard_service_config(cfg, 1) is cfg
    # a cap below the shard count floors at 1 per shard (0 would mean
    # "disabled"): the one documented over-admit case
    cfg.service.max_inflight_jobs = 2
    assert shard_service_config(cfg, 4).service.max_inflight_jobs == 1
    # disabled caps stay disabled
    cfg.service.max_inflight_jobs = 0
    assert shard_service_config(cfg, 4).service.max_inflight_jobs == 0


def test_admission_caps_hold_fleet_wide():
    """The satellite invariant: with global cap G over N shards, the
    fleet accepts at most ~G jobs — NOT G x N. Each shard enforces its
    ceil(G/N) share; stuffing both shards' stores shows rejection kicks
    in at the share, so the fleet-wide sum equals the global cap."""
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )

    cfg = FrameworkConfig.load(env={})
    cfg.service.max_inflight_jobs = 4
    per = shard_service_config(cfg, 2)
    assert per.service.max_inflight_jobs == 2

    accepted = 0
    for k in range(2):
        coord = Coordinator(config=per, shard_id=k, n_shards=2)
        for i in range(10):
            sid = coord.create_session()
            if coord.admission_check(sid) is not None:
                break
            # hold an unfinished job against the cap without dispatching
            coord.store.create_job(
                sid, f"j{k}-{i}", {"dataset_id": "iris"},
                [{"subtask_id": f"j{k}-{i}-subtask-0"}],
            )
            accepted += 1
        rejection = coord.admission_check(coord.create_session())
        assert rejection is not None and rejection["status"] == 429
    assert accepted == cfg.service.max_inflight_jobs  # == 4, not 8


# ---------------------------------------------------------------------
# QoS priority lanes
# ---------------------------------------------------------------------

def test_priority_subscription_orders_lanes():
    from cs230_distributed_machine_learning_tpu.runtime.queue import TopicBus

    bus = TopicBus()
    sub = bus.subscribe("tasks", priority=True)
    for prio, tag in [(0, "a"), (0, "b"), (5, "hot"), (1, "warm")]:
        bus.publish("tasks", {"priority": prio, "tag": tag})
    order = [sub.get(timeout=1)[1]["tag"] for _ in range(4)]
    assert order == ["hot", "warm", "a", "b"]  # lanes desc, FIFO within
    # plain subscriptions stay strict FIFO regardless of the field
    fifo = bus.subscribe("tasks2")
    for prio, tag in [(0, "a"), (9, "z")]:
        bus.publish("tasks2", {"priority": prio, "tag": tag})
    assert [fifo.get(timeout=1)[1]["tag"] for _ in range(2)] == ["a", "z"]


def test_session_priority_stamps_subtask_specs():
    from cs230_distributed_machine_learning_tpu.data.datasets import (
        materialize_builtin,
    )
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )

    materialize_builtin("iris")
    coord = Coordinator()
    sid = coord.create_session(priority=7)
    assert coord.store.session_priority(sid) == 7
    payload = {
        "dataset_id": "iris",
        "model_details": extract_model_details(
            LogisticRegression(max_iter=50)
        ),
        "train_params": {"test_size": 0.2, "random_state": 0},
    }
    submit = coord.submit_train(sid, dict(payload))
    job = coord.store.get_job(sid, submit["job_id"])
    specs = [s["spec"] for s in job["subtasks"].values()]
    assert specs and all(s["priority"] == 7 for s in specs)
    # a payload-level override beats the session lane
    submit2 = coord.submit_train(sid, {**payload, "priority": 2})
    job2 = coord.store.get_job(sid, submit2["job_id"])
    assert all(
        s["spec"]["priority"] == 2 for s in job2["subtasks"].values()
    )


def test_session_priority_survives_journal_replay(tmp_path):
    from cs230_distributed_machine_learning_tpu.runtime.store import JobStore

    store = JobStore(journal_dir=str(tmp_path))
    sid = store.create_session(priority=5)
    replayed = JobStore(journal_dir=str(tmp_path))
    assert replayed.session_priority(sid) == 5


# ---------------------------------------------------------------------
# SSE time-to-first-event
# ---------------------------------------------------------------------

def test_sse_prologue_padding_then_immediate_snapshot():
    """The /train_status stream must open with the buffer-defeating
    comment prologue and deliver the first progress snapshot immediately
    — NOT after a 1.5 s tick (the satellite fix behind the
    sse_first_event p50 drop in loadtest_4shard.json)."""
    from werkzeug.test import Client

    from cs230_distributed_machine_learning_tpu.data.datasets import (
        materialize_builtin,
    )
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )
    from cs230_distributed_machine_learning_tpu.runtime.server import (
        create_app,
    )

    materialize_builtin("iris")
    client = Client(create_app(Coordinator()))
    sid = client.post("/create_session").get_json()["session_id"]
    payload = {
        "dataset_id": "iris",
        "model_details": extract_model_details(
            LogisticRegression(max_iter=50)
        ),
        "train_params": {"test_size": 0.2, "random_state": 0},
    }
    resp = client.post(f"/train_status/{sid}", json=payload)
    t0 = time.perf_counter()
    it = iter(resp.response)
    first = next(it)
    first = first.decode() if isinstance(first, bytes) else first
    assert first.startswith(":") and len(first) >= 2048
    second = next(it)
    elapsed = time.perf_counter() - t0
    second = second.decode() if isinstance(second, bytes) else second
    assert second.startswith("data: ")
    snapshot = json.loads(second[len("data: "):].strip())
    assert "job_status" in snapshot and snapshot.get("job_id")
    # immediate: far inside one sse tick (1.5 s)
    assert elapsed < 1.0, f"first snapshot took {elapsed:.2f}s"
    resp.response.close()


# ---------------------------------------------------------------------
# live two-shard fleet behind two front ends (in-process, real sockets)
# ---------------------------------------------------------------------

@pytest.fixture()
def two_shard_fleet():
    from werkzeug.serving import make_server

    from cs230_distributed_machine_learning_tpu.data.datasets import (
        materialize_builtin,
    )
    from cs230_distributed_machine_learning_tpu.runtime.cluster import (
        ClusterRuntime,
    )
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )
    from cs230_distributed_machine_learning_tpu.runtime.frontend import (
        create_frontend_app,
    )
    from cs230_distributed_machine_learning_tpu.runtime.server import (
        create_app,
    )
    from cs230_distributed_machine_learning_tpu.utils.config import (
        get_config,
    )

    materialize_builtin("iris")
    cfg = shard_service_config(get_config(), 2)
    servers, clusters, shard_urls = [], [], []
    for k in range(2):
        cluster = ClusterRuntime(shard_id=k)
        cluster.add_executor()
        coord = Coordinator(
            config=cfg, cluster=cluster, shard_id=k, n_shards=2
        )
        srv = make_server(
            "127.0.0.1", 0, create_app(coord), threaded=True
        )
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        shard_urls.append(f"http://127.0.0.1:{srv.server_port}")
        servers.append(srv)
        clusters.append(cluster)
    fes = []
    for _ in range(2):
        fe = make_server(
            "127.0.0.1", 0, create_frontend_app(shard_urls), threaded=True
        )
        threading.Thread(target=fe.serve_forever, daemon=True).start()
        fes.append(fe)
    yield {
        "shards": shard_urls,
        "frontends": [f"http://127.0.0.1:{s.server_port}" for s in fes],
    }
    for s in servers + fes:
        s.shutdown()
    for c in clusters:
        c.shutdown()


def _submit(url, sid, job_id=None):
    payload = {
        "dataset_id": "iris",
        "model_details": extract_model_details(
            LogisticRegression(max_iter=50)
        ),
        "train_params": {"test_size": 0.2, "random_state": 0},
    }
    if job_id:
        payload["job_id"] = job_id
    r = requests.post(f"{url}/train/{sid}", json=payload, timeout=60)
    r.raise_for_status()
    return r.json()


def _wait_completed(url, sid, jid, timeout_s=180):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        body = requests.get(
            f"{url}/check_status/{sid}/{jid}", timeout=30
        ).json()
        if body.get("job_status") in (
            "completed", "failed", "completed_with_failures"
        ):
            return body
        time.sleep(0.2)
    raise TimeoutError(f"job {jid} never finished via {url}")


def test_job_via_any_frontend_visible_via_every_frontend(two_shard_fleet):
    """The satellite routing invariant end to end: session minted on FE0,
    job submitted through FE0 under a CLIENT-minted id, then polled,
    listed, streamed, and costed through FE1 — plus direct-to-shard
    checks that the stamp actually routed to the owning shard."""
    import uuid

    fe0, fe1 = two_shard_fleet["frontends"]
    shards = two_shard_fleet["shards"]

    body = requests.post(f"{fe0}/create_session", timeout=30).json()
    sid, owner = body["session_id"], body["shard"]
    assert owner == shard_of(sid, 2)  # FE-minted id routes consistently

    client_jid = str(uuid.uuid4())
    submit = _submit(fe0, sid, job_id=client_jid)
    jid = submit["job_id"]
    assert id_shard(jid) == owner  # stamped by the owning shard
    # idempotent resubmit under the client id dedupes to the same job
    dup = _submit(fe0, sid, job_id=client_jid)
    assert dup["job_id"] == jid and dup.get("duplicate") is True

    # visible through the OTHER front end
    final = _wait_completed(fe1, sid, jid)
    assert final["job_status"] == "completed"
    assert any(
        j["job_id"] == jid
        for j in requests.get(f"{fe1}/jobs", timeout=30).json()
    )
    # job-stamp-only routes work through any front end
    cost = requests.get(f"{fe1}/cost/{jid}", timeout=30)
    assert cost.status_code == 200 and cost.json()["job_id"] == jid

    # streamable through the other front end (SSE resume by job id —
    # reads the prologue + first snapshot, then closes)
    with requests.post(
        f"{fe1}/train_status/{sid}", json={"job_id": jid},
        stream=True, timeout=60,
    ) as r:
        assert r.status_code == 200
        got_event = False
        for line in r.iter_lines(chunk_size=1):
            if line.startswith(b"data: "):
                evt = json.loads(line[len(b"data: "):])
                assert evt["job_id"] == jid
                got_event = True
                break
        assert got_event
    # the job lives ONLY on its owning shard (state really is sharded)
    on_shard = [
        any(
            j["job_id"] == jid
            for j in requests.get(f"{u}/jobs", timeout=30).json()
        )
        for u in shards
    ]
    assert on_shard[owner] and not on_shard[1 - owner]


def test_worker_plane_routes_by_stamp(two_shard_fleet):
    fe0 = two_shard_fleet["frontends"][0]
    # round-robin assignment mints stamped ids on alternating shards
    w0 = requests.post(f"{fe0}/subscribe", json={}, timeout=30).json()
    w1 = requests.post(f"{fe0}/subscribe", json={}, timeout=30).json()
    k0, k1 = id_shard(w0["worker_id"]), id_shard(w1["worker_id"])
    assert {k0, k1} == {0, 1}
    # a pinned subscribe lands where asked
    wp = requests.post(
        f"{fe0}/subscribe", json={"shard": 1}, timeout=30
    ).json()
    assert id_shard(wp["worker_id"]) == 1
    # the stamp routes the whole worker plane through the front end
    for wid in (w0["worker_id"], w1["worker_id"], wp["worker_id"]):
        hb = requests.post(f"{fe0}/heartbeat/{wid}", timeout=30)
        assert hb.status_code == 200
        nt = requests.get(
            f"{fe0}/next_tasks/{wid}", params={"timeout": 0.05},
            timeout=30,
        )
        assert nt.status_code == 200 and nt.json()["tasks"] == []
        requests.post(f"{fe0}/unsubscribe/{wid}", timeout=30)
    # an unstamped worker id cannot be routed
    r = requests.get(f"{fe0}/next_tasks/worker-99", timeout=30)
    assert r.status_code == 404


def test_frontend_aggregates_fleet_views(two_shard_fleet):
    fe0 = two_shard_fleet["frontends"][0]
    hz = requests.get(f"{fe0}/healthz", timeout=30).json()
    assert hz["n_shards"] == 2 and set(hz["shards"]) == {"0", "1"} or set(
        hz["shards"]
    ) == {0, 1}
    assert requests.get(f"{fe0}/readyz", timeout=30).status_code == 200
    # merged exposition: every series carries a shard label, metadata
    # lines are deduped
    prom = requests.get(f"{fe0}/metrics/prom", timeout=30).text
    assert 'shard="0"' in prom and 'shard="1"' in prom
    helps = [
        line for line in prom.splitlines()
        if line.startswith("# HELP tpuml_http_requests_total")
    ]
    assert len(helps) == 1
    # workers merge on stamped ids: each shard's local executor shows up
    workers = requests.get(f"{fe0}/workers", timeout=30).json()
    assert {id_shard(w) for w in workers} == {0, 1}
    # dashboard-compatible aggregate shapes (the /dashboard JS polls
    # these expecting the coordinator's shapes, not a raw scatter)
    ev = requests.get(f"{fe0}/events?limit=10", timeout=30).json()
    assert isinstance(ev.get("events"), list)
    mh = requests.get(f"{fe0}/metrics/history", timeout=30).json()
    assert isinstance(mh.get("names"), list)
    assert isinstance(
        requests.get(f"{fe0}/supervisor", timeout=30).json(), list
    )


def test_shard_minted_sessions_hash_home(two_shard_fleet):
    """A bare POST /create_session DIRECTLY to a shard (no front-end
    mint) must return a session id that hashes to that shard — otherwise
    the session would be unreachable through every front end."""
    for k, url in enumerate(two_shard_fleet["shards"]):
        body = requests.post(f"{url}/create_session", timeout=30).json()
        assert body["shard"] == k
        assert shard_of(body["session_id"], 2) == k
    # a client-supplied id that hashes elsewhere is rejected, not stored
    sid = "fixed-session-id"
    wrong = 1 - shard_of(sid, 2)
    r = requests.post(
        f"{two_shard_fleet['shards'][wrong]}/create_session",
        json={"session_id": sid}, timeout=30,
    )
    assert r.status_code == 400


def test_frontend_prometheus_label_injection():
    from cs230_distributed_machine_learning_tpu.runtime.frontend import (
        _inject_shard_label,
    )

    body = (
        "# HELP m help\n# TYPE m counter\n"
        "m 3\n"
        'n{route="train",code="200"} 1.5\n'
    )
    lines = _inject_shard_label(body, 2)
    assert 'm{shard="2"} 3' in lines
    assert 'n{shard="2",route="train",code="200"} 1.5' in lines
    assert "# HELP m help" in lines


def test_qos_lane_aging_prevents_starvation():
    """ROADMAP item 2 follow-up: strict-priority lanes age — a waiting
    low-lane message is promoted one lane per qos_aging_s, so a
    sustained high-priority flood cannot starve lane 0 forever."""
    from cs230_distributed_machine_learning_tpu.runtime.queue import TopicBus

    bus = TopicBus()
    sub = bus.subscribe("tasks", priority=True, aging_s=0.05)
    bus.publish("tasks", {"priority": 0, "tag": "starved"})
    time.sleep(0.12)  # > 2 aging periods: promoted past lane 1
    for i in range(16):
        bus.publish("tasks", {"priority": 1, "tag": f"flood-{i}"})
    # the aged lane-0 message is delivered FIRST (promoted into lane >=1
    # with the oldest sequence number), not after the entire flood
    assert sub.get(timeout=1)[1]["tag"] == "starved"

    # aging off (<=0): pure strict priority, the flood wins
    strict = bus.subscribe("tasks2", priority=True, aging_s=0)
    bus.publish("tasks2", {"priority": 0, "tag": "low"})
    time.sleep(0.06)
    bus.publish("tasks2", {"priority": 1, "tag": "high"})
    assert strict.get(timeout=1)[1]["tag"] == "high"


def test_frontend_streams_large_bodies_zero_copy():
    """ROADMAP item 2 follow-up: the front end relays large request
    bodies to the owning shard chunk-wise (Content-Length preserved,
    body bit-identical) WITHOUT buffering the whole body per hop —
    pinned by forbidding Request.get_data for large payloads."""
    import hashlib
    import http.server
    import os

    from werkzeug.test import Client
    from werkzeug.wrappers import Request

    from cs230_distributed_machine_learning_tpu.runtime import frontend as fe

    received = {}

    class EchoShard(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            length = int(self.headers.get("Content-Length", 0))
            h = hashlib.sha1()
            remaining = length
            while remaining > 0:
                chunk = self.rfile.read(min(65536, remaining))
                if not chunk:
                    break
                h.update(chunk)
                remaining -= len(chunk)
            if not self.path.startswith("/trace_spans"):
                # the front end ships its frontend.proxy span to the shard
                # after the relay: that POST may land before the asserts
                received["sha1"] = h.hexdigest()
                received["length"] = length
                received["te"] = self.headers.get("Transfer-Encoding")
            body = json.dumps({"status": "ok"}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # quiet
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), EchoShard)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        app = fe.create_frontend_app(
            [f"http://127.0.0.1:{srv.server_address[1]}"]
        )
        client = Client(app)
        big = os.urandom(2 * fe._STREAM_BODY_MIN)  # 512 KB

        original_get_data = Request.get_data

        def guarded_get_data(self, *a, **kw):
            if (self.content_length or 0) >= fe._STREAM_BODY_MIN:
                raise AssertionError(
                    "front end buffered a large body via get_data()"
                )
            return original_get_data(self, *a, **kw)

        Request.get_data = guarded_get_data
        try:
            resp = client.post(
                "/train/some-session", data=big,
                content_type="application/octet-stream",
            )
        finally:
            Request.get_data = original_get_data
        assert resp.status_code == 200
        assert received["length"] == len(big)
        assert received["sha1"] == hashlib.sha1(big).hexdigest()
        # streamed with a declared length, not chunked transfer-encoding
        assert received["te"] is None

        # small bodies keep the simple buffered path (and still arrive)
        small = b'{"x": 1}'
        resp = client.post(
            "/train/some-session", data=small,
            content_type="application/json",
        )
        assert resp.status_code == 200
        assert received["length"] == len(small)
    finally:
        srv.shutdown()
