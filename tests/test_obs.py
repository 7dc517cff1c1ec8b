"""Obs layer: registry thread-safety, exposition format, tracer, valve."""

import re
import threading

import pytest

from cs230_distributed_machine_learning_tpu.obs import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    Tracer,
    activate,
    counter_inc,
    current_trace_id,
    observe,
    span,
    use_tracer,
)
from cs230_distributed_machine_learning_tpu.obs import tracing as tracing_mod


# ---------------- registry ----------------


def test_counter_thread_safety_under_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "test")
    n_threads, n_incs = 8, 2000

    def worker():
        for _ in range(n_incs):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == n_threads * n_incs


def test_histogram_thread_safety_under_concurrent_observes():
    reg = MetricsRegistry()
    h = reg.histogram("t_seconds", "test")
    n_threads, n_obs = 8, 1000

    def worker(i):
        for k in range(n_obs):
            h.observe(0.001 * (k % 7))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count() == n_threads * n_obs


def _parse_prom(text):
    """Minimal Prometheus text-format parser: returns ({name: (type, help)},
    {sample_name_with_labels: value})."""
    families, samples = {}, {}
    for line in text.splitlines():
        if not line or line.isspace():
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            families.setdefault(name, [None, help_text])
        elif line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram"), line
            families.setdefault(name, [None, ""])[0] = kind
        else:
            m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$", line)
            assert m, f"unparseable sample line: {line!r}"
            samples[m.group(1) + (m.group(2) or "")] = m.group(3)
    return families, samples


def test_histogram_buckets_and_exposition_format():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    families, samples = _parse_prom(reg.render())
    assert families["lat_seconds"][0] == "histogram"
    # cumulative bucket semantics
    assert samples['lat_seconds_bucket{le="0.1"}'] == "1"
    assert samples['lat_seconds_bucket{le="1"}'] == "3"
    assert samples['lat_seconds_bucket{le="10"}'] == "4"
    assert samples['lat_seconds_bucket{le="+Inf"}'] == "5"
    assert samples["lat_seconds_count"] == "5"
    assert float(samples["lat_seconds_sum"]) == pytest.approx(56.05)


def test_histogram_boundary_lands_in_its_bucket():
    # le is an UPPER bound: an observation exactly on a bound counts there
    reg = MetricsRegistry()
    h = reg.histogram("b_seconds", "b", buckets=(1.0, 2.0))
    h.observe(1.0)
    _, samples = _parse_prom(reg.render())
    assert samples['b_seconds_bucket{le="1"}'] == "1"


def test_counter_labels_render_and_accumulate():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc(endpoint="train")
    c.inc(endpoint="train")
    c.inc(endpoint="health")
    _, samples = _parse_prom(reg.render())
    assert samples['req_total{endpoint="train"}'] == "2"
    assert samples['req_total{endpoint="health"}'] == "1"
    assert c.value(endpoint="train") == 2


def test_registered_families_expose_at_zero():
    reg = MetricsRegistry()
    reg.counter("zero_total", "never incremented")
    reg.histogram("zero_seconds", "never observed")
    families, samples = _parse_prom(reg.render())
    assert families["zero_total"][0] == "counter"
    assert samples["zero_total"] == "0"
    assert samples["zero_seconds_count"] == "0"


def test_label_values_escaped_in_exposition():
    # label values can arrive off the wire (a remote agent's algo name):
    # quotes/backslashes/newlines must not break the whole scrape
    reg = MetricsRegistry()
    c = reg.counter("esc_total", "escaping")
    c.inc(model='My"Model\\v1\n')
    rendered = "\n".join(c.render())
    assert 'model="My\\"Model\\\\v1\\n"' in rendered
    # the raw value still reads back through the API
    assert c.value(model='My"Model\\v1\n') == 1


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")


def test_global_catalog_registered():
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    names = REGISTRY.names()
    for required in (
        "tpuml_subtasks_dispatched_total",
        "tpuml_subtasks_completed_total",
        "tpuml_subtasks_failed_total",
        "tpuml_subtasks_requeued_total",
        "tpuml_scheduler_placement_seconds",
        "tpuml_executor_compile_seconds",
        "tpuml_executor_stage_seconds",
        "tpuml_executor_dispatch_seconds",
        "tpuml_executor_fetch_seconds",
        "tpuml_executable_cache_hits_total",
        "tpuml_executable_cache_misses_total",
    ):
        assert required in names


# ---------------- tracer ----------------


def test_span_nesting_builds_tree():
    t = Tracer(journal=False)
    with use_tracer(t):
        with span("root", trace_id="trace0001") as root:
            with span("child_a"):
                with span("grandchild"):
                    pass
            with span("child_b"):
                pass
    tree = t.tree("trace0001")
    assert len(tree) == 1 and tree[0]["name"] == "root"
    kids = [c["name"] for c in tree[0]["children"]]
    assert kids == ["child_a", "child_b"]
    assert tree[0]["children"][0]["children"][0]["name"] == "grandchild"
    assert root.trace_id == "trace0001"


def test_activate_propagates_trace_id_to_spans():
    t = Tracer(journal=False)
    with use_tracer(t):
        with activate("feedface00000000"):
            assert current_trace_id() == "feedface00000000"
            with span("inside"):
                pass
    spans = t.spans_for("feedface00000000")
    assert [s["name"] for s in spans] == ["inside"]


def test_job_binding_and_span_ordering():
    t = Tracer(journal=False)
    t.bind_job("job-1", "aaaa000011112222")
    assert t.trace_for_job("job-1") == "aaaa000011112222"
    assert t.trace_for_job("nope") is None


def test_ring_buffer_evicts_oldest_whole_trace():
    t = Tracer(journal=False)
    n = tracing_mod._MAX_TRACES + 5
    with use_tracer(t):
        for i in range(n):
            with span("s", trace_id=f"trace{i:011d}"):
                pass
    kept = t.traces()
    assert len(kept) == tracing_mod._MAX_TRACES
    assert f"trace{0:011d}" not in kept
    assert f"trace{n - 1:011d}" in kept


def test_ingest_accepts_remote_spans_and_drops_malformed():
    t = Tracer(journal=False)
    good = {
        "trace_id": "cafe000000000000",
        "span_id": "01234567",
        "parent_id": None,
        "name": "remote.batch",
        "start": 1.0,
        "end": 2.0,
        "attrs": {},
        "process": "pid:999",
    }
    n = t.ingest([good, {"no": "ids"}, "junk", None])
    assert n == 1
    assert [s["name"] for s in t.spans_for("cafe000000000000")] == ["remote.batch"]


def test_pending_drain_collects_and_clears():
    t = Tracer(pending=True, journal=False)
    with use_tracer(t):
        with span("a", trace_id="d00d000000000000"):
            pass
    drained = t.drain()
    assert [s["name"] for s in drained] == ["a"]
    assert t.drain() == []
    # spans stay queryable after draining (drain feeds the REST shipment,
    # not the local ring)
    assert len(t.spans_for("d00d000000000000")) == 1


def test_error_span_records_and_reraises():
    t = Tracer(journal=False)
    with use_tracer(t):
        with pytest.raises(RuntimeError):
            with span("boom", trace_id="beef000000000000"):
                raise RuntimeError("kaput")
    (s,) = t.spans_for("beef000000000000")
    assert "RuntimeError" in s["attrs"]["error"]


# ---------------- disabled valve ----------------


def test_disabled_valve_is_a_noop(monkeypatch):
    monkeypatch.setenv("CS230_OBS", "0")
    t = Tracer(journal=False)
    with use_tracer(t):
        with span("invisible", trace_id="0123000000000000") as sp:
            # the shared no-op handle tolerates the instrumentation surface
            sp.attrs["x"] = 1
            sp.start = 123.0
            assert sp.span_id is None
    assert t.traces() == []

    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    before = REGISTRY.counter("tpuml_jobs_submitted_total").value()
    counter_inc("tpuml_jobs_submitted_total")
    observe("tpuml_executor_fetch_seconds", 1.0)
    assert REGISTRY.counter("tpuml_jobs_submitted_total").value() == before


def test_journal_writes_spans_jsonl(tmp_path, monkeypatch):
    """Spans land in <journal_dir>/spans.jsonl (the storage root is
    per-test via conftest's _tmp_storage fixture)."""
    import json
    import os

    from cs230_distributed_machine_learning_tpu.utils.config import get_config

    # CI pins the journal elsewhere (deploy/ci.sh CS230_JOURNAL_DIR);
    # this test asserts the default config-derived location
    monkeypatch.delenv("CS230_JOURNAL_DIR", raising=False)
    t = Tracer(journal=True)
    with use_tracer(t):
        with span("journaled", trace_id="abcd000000000000", tracer=t):
            pass
    path = os.path.join(get_config().storage.journal_dir, "spans.jsonl")
    assert os.path.exists(path)
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert any(e["name"] == "journaled" for e in lines)


def test_journal_dir_env_override(tmp_path, monkeypatch):
    """CS230_JOURNAL_DIR pins the span journal to one place regardless of
    the configured storage root — the CI artifact-collection contract
    (deploy/ci.sh)."""
    import json
    import os

    override = tmp_path / "ci-journal"
    monkeypatch.setenv("CS230_JOURNAL_DIR", str(override))
    t = Tracer(journal=True)
    with use_tracer(t):
        with span("ci-span", trace_id="abcd000000000001", tracer=t):
            pass
    path = override / "spans.jsonl"
    assert path.exists()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert any(e["name"] == "ci-span" for e in lines)
