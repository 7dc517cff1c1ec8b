"""Critical-path engine, trace diffing, export formats, and the span-drop
accounting (docs/OBSERVABILITY.md "Critical path & trace export").

The engine tests run on SYNTHETIC spans/timelines with hand-picked
timestamps, so every expected segment duration is exact arithmetic — the
invariants pinned here (segments tile the window, sum == wall, gaps
surface as ``untraced``, only the winning attempt charges) are the
contract the live ``GET /critical_path/<job_id>`` report inherits."""

import json
import uuid

import pytest

from cs230_distributed_machine_learning_tpu.obs import (
    REGISTRY,
    TRACER,
    compare_critical_paths,
    critical_path,
    export_trace,
    to_otlp,
    to_perfetto,
)
from cs230_distributed_machine_learning_tpu.obs import tracing
from cs230_distributed_machine_learning_tpu.obs.tracing import Tracer

#: fixed epoch base: offsets below are seconds into the synthetic job
T = 1_700_000_000.0


def _span(name, start, end, *, sid=None, parent=None, attrs=None,
          process="pid:1", tid="aaaabbbbccccdddd"):
    return {
        "trace_id": tid,
        "span_id": sid or uuid.uuid4().hex[:8],
        "parent_id": parent,
        "name": name,
        "start": T + start,
        "end": T + end,
        "attrs": attrs or {},
        "process": process,
    }


def _ev(kind, ts, *, stid="st1", attempt=0, worker=None, data=None):
    return {
        "ts": T + ts,
        "kind": kind,
        "job_id": "job-1",
        "subtask_id": stid,
        "worker_id": worker,
        "attempt": attempt,
        "data": data or {},
        "seq": 0,
    }


def _happy_scenario(aggregate_end=10.0):
    """submit -> expand -> queue -> place -> batch(phases) -> ingest ->
    [1 s untraced] -> aggregate. Window [0, aggregate_end]."""
    batch = _span("executor.batch", 1.0, 7.0, sid="batch1234",
                  attrs={"worker": "w1"})
    spans = [
        _span("http.train", 0.0, 0.5),
        _span("job.submit", 0.05, 0.45),
        _span("job.expand", 0.1, 0.3),
        _span("job.execute", 0.5, 9.0),
        _span("schedule.place", 0.9, 1.0,
              attrs={"subtask_id": "st1", "worker": "w1", "attempt": 0}),
        batch,
        _span("executor.compile", 1.0, 3.0, parent="batch1234"),
        _span("executor.dispatch", 3.0, 6.5, parent="batch1234"),
        _span("executor.fetch", 6.5, 7.0, parent="batch1234"),
        _span("job.aggregate", 9.0, aggregate_end),
    ]
    timelines = {
        "st1": [
            _ev("placement", 1.0, worker="w1"),
            _ev("result", 8.0, worker="w1", data={"status": "completed"}),
        ],
        # non-critical sibling: finished earlier, must not be picked
        "st0": [
            _ev("placement", 1.0, stid="st0", worker="w2"),
            _ev("result", 5.0, stid="st0", worker="w2",
                data={"status": "completed"}),
        ],
    }
    return spans, timelines


def _assert_tiles(report):
    """The exactness contract: segments tile [t0, t1] contiguously and
    their durations sum to the wall — no overlap, no absorption."""
    segs = report["segments"]
    assert segs[0]["start"] == pytest.approx(report["t0"])
    assert segs[-1]["end"] == pytest.approx(report["t1"])
    for a, b in zip(segs, segs[1:]):
        assert a["end"] == pytest.approx(b["start"])
    assert sum(s["duration_s"] for s in segs) == pytest.approx(
        report["wall_s"], rel=1e-9
    )


# ---------------- engine ----------------


def test_exact_tiling_with_untraced_gap():
    spans, timelines = _happy_scenario()
    r = critical_path("job-1", trace_id="aaaabbbbccccdddd", spans=spans,
                      timelines=timelines, job_wall_s=10.2)
    assert r["wall_s"] == pytest.approx(10.0)
    assert r["job_wall_s"] == 10.2
    _assert_tiles(r)
    # the [8.0, 9.0] hole (result landed, aggregate not yet started, no
    # span covers it) surfaces as untraced — never silently absorbed
    assert r["untraced_s"] == pytest.approx(1.0)
    assert r["coverage"] == pytest.approx(0.9)
    assert "untraced" in r["totals"]
    # the decomposition found every stage of the pipeline
    for name in ("submit.http", "submit", "expand", "queue.wait", "place",
                 "executor.compile", "executor.dispatch", "executor.fetch",
                 "result.ingest", "aggregate"):
        assert name in r["totals"], name
    # phases out-rank the raw execute window wherever they cover it (the
    # batch [1, 7] is fully phase-covered here, so no bare "execute")
    assert r["totals"]["executor.dispatch"] == pytest.approx(3.5)
    assert r["totals"]["result.ingest"] == pytest.approx(1.0)
    assert r["totals"]["queue.wait"] == pytest.approx(0.4)  # 0.5 -> 0.9
    assert r["critical_subtask"] == "st1"
    assert r["winning_worker"] == "w1"
    assert r["winning_attempt"] == 0
    assert r["n_attempts"] == 1
    assert r["speculated"] is False
    # dominant ranking leads with the biggest consumer
    assert r["dominant"][0] == "executor.dispatch"


def test_frontend_proxy_span_anchors_window():
    spans, timelines = _happy_scenario()
    spans.append(_span("frontend.proxy", -0.2, 0.6,
                       attrs={"route": "train"}, process="frontend:9"))
    r = critical_path("job-1", trace_id="aaaabbbbccccdddd", spans=spans,
                      timelines=timelines)
    assert r["t0"] == pytest.approx(T - 0.2)
    assert r["wall_s"] == pytest.approx(10.2)
    _assert_tiles(r)
    # the pre-shard hop [−0.2, 0] is attributed, not untraced ...
    assert r["segments"][0]["name"] == "frontend.proxy"
    # ... but inside the shard every more-specific candidate out-ranks it
    assert r["totals"]["frontend.proxy"] == pytest.approx(0.2)


def test_no_spans_returns_none():
    assert critical_path("job-x", trace_id=None, spans=[]) is None


def test_reclaim_wait_of_hung_worker_charges_critical_path():
    """Satellite: a hung worker's lease-reclaim wait IS wall time the job
    spent — it must appear as its own segment, attributed to the
    superseded attempt, not vanish into untraced."""
    spans = [
        _span("job.submit", 0.0, 0.2),
        _span("job.execute", 0.2, 12.0),
        _span("schedule.place", 0.4, 0.5,
              attrs={"subtask_id": "st1", "worker": "w0", "attempt": 0}),
        _span("schedule.place", 5.5, 5.6,
              attrs={"subtask_id": "st1", "worker": "w1", "attempt": 1}),
        _span("executor.batch", 5.6, 9.6, attrs={"worker": "w1"}),
        _span("job.aggregate", 12.0, 12.5),
    ]
    timelines = {"st1": [
        _ev("placement", 0.5, attempt=0, worker="w0"),
        _ev("lease.reclaim", 5.5, attempt=0, worker="w0",
            data={"overdue_s": 2.0}),
        _ev("placement", 5.6, attempt=1, worker="w1"),
        _ev("result", 10.0, attempt=1, worker="w1",
            data={"status": "completed"}),
    ]}
    r = critical_path("job-1", trace_id="aaaabbbbccccdddd", spans=spans,
                      timelines=timelines)
    _assert_tiles(r)
    assert r["n_reclaims"] == 1
    assert r["n_attempts"] == 2
    assert r["winning_attempt"] == 1
    assert r["winning_worker"] == "w1"
    # hung from attempt-0 placement (0.5) to the sweep (5.5), minus the
    # attempt-1 place span? no — place@[5.5,5.6] starts AT the reclaim:
    # the full 5 s wait is reclaim.wait
    assert r["totals"]["reclaim.wait"] == pytest.approx(5.0)
    rec = next(s for s in r["segments"] if s["name"] == "reclaim.wait")
    assert rec["detail"]["attempt"] == 0
    assert rec["detail"]["worker"] == "w0"
    assert rec["detail"]["overdue_s"] == 2.0
    # only the retry's batch charges execute
    ex = [s for s in r["segments"] if s["name"] == "execute"]
    assert ex and all(s["detail"]["worker"] == "w1" for s in ex)


def test_speculative_win_charges_only_winner():
    """Satellite: the speculative loser's (long) executor window must not
    enter the decomposition — only the winning attempt's batch does."""
    spans = [
        _span("job.submit", 0.0, 0.2),
        _span("job.execute", 0.2, 7.0),
        _span("executor.batch", 0.6, 6.8, attrs={"worker": "w0"}),  # loser
        _span("executor.batch", 3.2, 5.9, attrs={"worker": "w1"}),  # winner
        _span("job.aggregate", 7.0, 7.2),
    ]
    timelines = {"st1": [
        _ev("placement", 0.5, attempt=0, worker="w0"),
        _ev("speculate.launch", 3.0, attempt=1, worker="w1"),
        _ev("placement", 3.1, attempt=1, worker="w1"),
        _ev("speculate.win", 6.0, attempt=1, worker="w1"),
        _ev("result", 6.0, attempt=1, worker="w1",
            data={"status": "completed"}),
    ]}
    r = critical_path("job-1", trace_id="aaaabbbbccccdddd", spans=spans,
                      timelines=timelines)
    _assert_tiles(r)
    assert r["speculated"] is True
    assert r["winning_worker"] == "w1"
    # execute == the winner's [3.2, 5.9] window, nothing from w0's 6.2 s
    assert r["totals"]["execute"] == pytest.approx(2.7)
    assert all(s["detail"].get("worker") != "w0"
               for s in r["segments"] if s["name"] == "execute")
    # the loser's overlap-only time shows up honestly as untraced
    assert r["untraced_s"] > 2.0


def _direct_scenario():
    """A local job: the client's span brackets everything, nothing is
    placed, and the result events name no worker."""
    spans = [
        _span("client.train", 0.0, 10.0, sid="c1000000"),
        _span("client.submit", 0.0, 0.3, parent="c1000000"),
        _span("job.submit", 0.05, 0.25),
        _span("job.expand", 0.1, 0.2),
        _span("client.wait", 0.3, 10.0, parent="c1000000"),
        _span("job.execute", 0.4, 9.5),
        _span("executor.batch", 0.5, 9.4, sid="bd000001",
              attrs={"worker": "exec-0"}),
        _span("executor.split_plan", 0.5, 2.5, parent="bd000001"),
        _span("executor.stage", 2.5, 2.6, sid="sg000001", parent="bd000001"),
        # the upload inside a mesh stage: a grandchild, covered by its parent
        _span("executor.stage", 2.52, 2.58, parent="sg000001"),
        _span("executor.dispatch", 2.7, 2.8, parent="bd000001"),
        _span("executor.fetch", 2.8, 9.0, parent="bd000001"),
        _span("executor.emit", 9.0, 9.4, parent="bd000001"),
        _span("job.aggregate", 9.5, 9.9),
    ]
    timelines = {"st1": [
        _ev("result", 9.2, data={"status": "completed"}),
    ]}
    return spans, timelines


@pytest.mark.parametrize("with_timelines", [True, False])
def test_direct_job_admits_its_batches_by_trace(with_timelines):
    """No placement, no winner to name: every ``executor.batch`` of the
    job's trace is on its path, tiled by its real children; what no span
    names stays ``untraced``, and the wall is the client's."""
    spans, timelines = _direct_scenario()
    r = critical_path("job-1", trace_id="aaaabbbbccccdddd", spans=spans,
                      timelines=timelines if with_timelines else None)
    _assert_tiles(r)
    assert r["wall_s"] == pytest.approx(10.0)  # client.train, start to end
    assert r["winning_worker"] is None
    assert r["totals"]["executor.split_plan"] == pytest.approx(2.0)
    assert r["totals"]["executor.stage"] == pytest.approx(0.1)
    assert r["totals"]["executor.fetch"] == pytest.approx(6.2)
    assert r["totals"]["executor.emit"] == pytest.approx(0.4)
    assert r["totals"]["execute"] == pytest.approx(0.1, abs=1e-5)  # 2.6-2.7, in no child
    assert r["totals"]["aggregate"] == pytest.approx(0.4)
    # 0.25-0.5 (thread start), 9.4-9.5, 9.9-10.0 (wake-up and result read)
    assert r["untraced_s"] == pytest.approx(0.05 + 0.25 + 0.1 + 0.1, abs=1e-5)
    assert r["dominant"][0] == "executor.fetch"


@pytest.mark.parametrize("phase, window, child, key, value, inner, share_s", [
    # the host waiting for the fit, apart from moving its bytes
    ("executor.fetch", (2.8, 9.0), "executor.wait", "on", "result", (2.81, 8.9), 6.09),
    # a chunked enqueue held back by the plan's bound on steps in flight
    ("executor.dispatch", (2.7, 2.8), "executor.wait", "on", "backpressure", (2.72, 2.79), 0.07),
    # a fresh executable: the cost analysis' own trace inside the build
    ("executor.compile", (2.6, 2.7), "executor.build", "stage", "cost", (2.61, 2.69), 0.08),
])
def test_a_phase_share_is_named_by_the_span_inside_it(
        phase, window, child, key, value, inner, share_s):
    """An ``executor.wait`` inside a fetch or a dispatch relabels that part
    of the phase as waiting (``on`` in the detail), an ``executor.build``
    inside a compile names the stage; the phase keeps what is left, and a
    wait or build under any other parent names nothing."""
    spans, timelines = _direct_scenario()
    spans = [s for s in spans if s["name"] != phase]
    spans.append(_span(phase, *window, sid="ph000001", parent="bd000001"))
    spans.append(_span(child, *inner, parent="ph000001", attrs={key: value}))
    spans.append(_span(child, 9.05, 9.3, parent="bd000001", attrs={key: "stray"}))
    r = critical_path("job-1", trace_id="aaaabbbbccccdddd", spans=spans,
                      timelines=timelines)
    _assert_tiles(r)
    assert r["totals"][child] == pytest.approx(share_s, abs=1e-5)
    assert r["totals"][phase] == pytest.approx(
        window[1] - window[0] - share_s, abs=1e-5)
    (seg,) = [s for s in r["segments"] if s["name"] == child]
    assert seg["detail"] == {key: value}
    assert r["totals"]["executor.emit"] == pytest.approx(0.4)  # the stray names nothing
    assert set(r["totals"]) & {"executor.wait", "executor.build"} == {child}


def test_scheduled_job_keeps_the_winner_only_rule():
    """A placed job whose result has not named a worker yet admits no
    batch: the direct rule is for jobs that were never placed."""
    spans = [
        _span("job.submit", 0.0, 0.2),
        _span("job.execute", 0.2, 5.0),
        _span("executor.batch", 0.6, 4.0, sid="bs000001",
              attrs={"worker": "w0"}),
        _span("executor.dispatch", 0.7, 3.9, parent="bs000001"),
    ]
    timelines = {"st1": [_ev("placement", 0.5, worker="w0")]}
    r = critical_path("job-1", trace_id="aaaabbbbccccdddd", spans=spans,
                      timelines=timelines)
    _assert_tiles(r)
    assert "execute" not in r["totals"]
    assert "executor.dispatch" not in r["totals"]


def test_compare_attributes_injected_slowdown():
    spans_a, tl = _happy_scenario(aggregate_end=10.0)
    spans_b, _ = _happy_scenario(aggregate_end=15.0)  # +5 s in aggregate
    a = critical_path("job-a", trace_id="a" * 16, spans=spans_a, timelines=tl)
    b = critical_path("job-b", trace_id="b" * 16, spans=spans_b, timelines=tl)
    diff = compare_critical_paths(a, b)
    assert diff["delta_wall_s"] == pytest.approx(5.0)
    assert diff["dominant_segment"] == "aggregate"
    # rows ranked by |delta|: the injected slowdown leads and owns ~all
    # of the wall delta
    assert diff["segments"][0]["name"] == "aggregate"
    assert diff["segments"][0]["share_of_delta"] >= 0.8
    assert diff["job_a"] == "job-a" and diff["job_b"] == "job-b"


# ---------------- export formats ----------------


def test_perfetto_export_is_valid_chrome_trace():
    spans, _ = _happy_scenario()
    doc = to_perfetto(spans)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert len(xs) == len(spans)
    assert ms and all(e["name"] == "process_name" for e in ms)
    for e in xs:
        assert set(e) >= {"name", "cat", "pid", "tid", "ts", "dur", "args"}
        assert e["ts"] >= 0 and e["dur"] >= 0  # relative microseconds
    # phase children sit one track below their batch parent
    batch = next(e for e in xs if e["name"] == "executor.batch")
    compile_ = next(e for e in xs if e["name"] == "executor.compile")
    assert compile_["tid"] == batch["tid"] + 1
    # the document is valid JSON end to end
    assert json.loads(json.dumps(doc))["traceEvents"]


def test_otlp_export_shapes():
    spans, _ = _happy_scenario()
    doc = to_otlp(spans)
    rs = doc["resourceSpans"]
    assert rs, "one resourceSpans entry per process expected"
    entries = [s for r in rs for sc in r["scopeSpans"] for s in sc["spans"]]
    assert len(entries) == len(spans)
    for s in entries:
        assert len(s["traceId"]) == 32
        assert len(s["spanId"]) == 16
        assert int(s["startTimeUnixNano"]) <= int(s["endTimeUnixNano"])
    with_parent = [s for s in entries if "parentSpanId" in s]
    assert with_parent and all(
        len(s["parentSpanId"]) == 16 for s in with_parent
    )


def test_export_trace_writes_under_journal_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("CS230_JOURNAL_DIR", str(tmp_path))
    spans, _ = _happy_scenario()
    out = export_trace("feedbeef00000001", spans, "perfetto", job_id="job-1")
    assert out["format"] == "perfetto"
    assert out["n_spans"] == len(spans)
    assert out["path"] and out["path"].endswith(
        "trace_feedbeef00000001.perfetto.json"
    )
    with open(out["path"]) as f:
        assert json.load(f)["traceEvents"]
    with pytest.raises(ValueError):
        export_trace("feedbeef00000001", spans, "jaeger")


# ---------------- span-drop accounting ----------------


def test_trace_eviction_is_lru_and_counted(monkeypatch):
    """Satellite: ring overflow evicts the least-recently-TOUCHED whole
    trace (not merely insertion order) and every dropped span lands in
    tpuml_trace_spans_dropped_total{reason=trace_evicted}."""
    monkeypatch.setattr(tracing, "_MAX_TRACES", 2)
    ctr = REGISTRY.counter("tpuml_trace_spans_dropped_total")
    before = ctr.value(reason="trace_evicted")
    t = Tracer(journal=False)
    t.record(_span("a", 0, 1, tid="t1" * 8))
    t.record(_span("a", 0, 1, tid="t1" * 8))
    t.record(_span("b", 0, 1, tid="t2" * 8))
    t.record(_span("a2", 1, 2, tid="t1" * 8))  # touch t1: now t2 is LRU
    t.record(_span("c", 0, 1, tid="t3" * 8))  # overflow -> evict t2
    assert set(t.traces()) == {"t1" * 8, "t3" * 8}
    assert len(t.spans_for("t1" * 8)) == 3
    assert ctr.value(reason="trace_evicted") == before + 1  # t2's one span


def test_per_trace_span_cap_counted(monkeypatch):
    monkeypatch.setattr(tracing, "_MAX_SPANS_PER_TRACE", 2)
    ctr = REGISTRY.counter("tpuml_trace_spans_dropped_total")
    before = ctr.value(reason="trace_full")
    t = Tracer(journal=False)
    for i in range(5):
        t.record(_span(f"s{i}", i, i + 1, tid="tf" * 8))
    assert len(t.spans_for("tf" * 8)) == 2  # cap held
    assert ctr.value(reason="trace_full") == before + 3


# ---------------- REST surface ----------------


@pytest.fixture()
def client():
    from werkzeug.test import Client

    from cs230_distributed_machine_learning_tpu.runtime.coordinator import (
        Coordinator,
    )
    from cs230_distributed_machine_learning_tpu.runtime.server import (
        create_app,
    )

    return Client(create_app(Coordinator()))


def _bind_synthetic_job(job_id, tid):
    spans, _ = _happy_scenario()
    for s in spans:
        s["trace_id"] = tid
        TRACER.record(s)
    TRACER.bind_job(job_id, tid)


def test_critical_path_endpoint_and_compare(client):
    _bind_synthetic_job("job-cp-a", "11112222333344aa")
    _bind_synthetic_job("job-cp-b", "11112222333344bb")
    r = client.get("/critical_path/job-cp-a")
    assert r.status_code == 200
    body = r.get_json()
    assert body["job_id"] == "job-cp-a"
    assert body["segments"] and body["dominant"]
    assert sum(s["duration_s"] for s in body["segments"]) == pytest.approx(
        body["wall_s"], rel=1e-6
    )
    # diff rider
    r = client.get("/critical_path/job-cp-b?compare=job-cp-a")
    assert r.status_code == 200
    assert r.get_json()["diff"]["job_a"] == "job-cp-a"
    # unknown ids 404 (both positions)
    assert client.get("/critical_path/nope").status_code == 404
    assert (
        client.get("/critical_path/job-cp-a?compare=nope").status_code == 404
    )


def test_trace_export_endpoint(client, tmp_path, monkeypatch):
    monkeypatch.setenv("CS230_JOURNAL_DIR", str(tmp_path))
    _bind_synthetic_job("job-exp", "11112222333344cc")
    r = client.get("/trace/job-exp/export")
    assert r.status_code == 200
    body = r.get_json()
    assert body["format"] == "perfetto"
    assert body["document"]["traceEvents"]
    assert body["path"] and json.load(open(body["path"]))["traceEvents"]
    r = client.get("/trace/job-exp/export?format=otlp")
    assert r.status_code == 200
    assert r.get_json()["document"]["resourceSpans"]
    assert client.get("/trace/job-exp/export?format=zipkin").status_code == 400
    assert client.get("/trace/nope/export").status_code == 404


def test_home_lists_new_endpoints(client):
    eps = "\n".join(client.get("/").get_json()["endpoints"])
    assert "/critical_path/" in eps
    assert "/trace/<job_id>/export" in eps
