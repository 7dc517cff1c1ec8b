"""CPU tests of the per-layer metrics that read the waits, the build stages
and the thread CPU time on the program's spans (PR 37).

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_wait_metrics.py -q

Nothing here is a measurement: a CPU run proves counts and control flow.
"""

import math
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from test_perfbench import _run_toy, run  # noqa: E402 — the harness's own toy root

WINDOW_METRICS = ("device_wait_ms", "host_enqueue_ms")
FIRST_METRICS = ("first_search_untraced_pct", "first_build_s", "first_cost_trace_s",
                 "first_host_prep_s", "first_device_wait_s")


@pytest.mark.parametrize("workload", ["logreg_rows5m.rs128", "mlp_mnist.rs64"])
def test_traced_toy_run_reports_the_wait_metrics(tmp_path, workload):
    r, values = _run_toy(tmp_path, workload, trace=True)
    assert r["correct"] is True
    for name in WINDOW_METRICS + FIRST_METRICS:
        value = r["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    m = {name: r["metrics"][name]["value"] for name in r["metrics"]}
    mean_wall_ms = 1e3 * sum(values["search_walls_s"]) / values["searches"]
    # waiting and enqueueing are disjoint parts of a search, as are the
    # host's shares before the first dispatch and after the last fetch
    assert m["device_wait_ms"] + m["host_enqueue_ms"] <= mean_wall_ms
    assert (m["pre_dispatch_host_ms"] + m["host_enqueue_ms"] + m["device_wait_ms"]
            + m["post_fetch_host_ms"]) <= mean_wall_ms * 1.001
    # the first search built executables, staged the data and waited
    assert m["first_build_s"] > 0 and m["first_cost_trace_s"] <= m["first_build_s"]
    assert m["first_device_wait_s"] > 0 and m["first_host_prep_s"] > 0
    assert m["first_search_untraced_pct"] < 50


# The eight spans a program from before PR 26 records (as in
# ``test_span_metrics.py::test_span_readers_find_nothing_in_a_program_without_the_spans``)
BEFORE_PR26 = [("client.train", 0.0, 0.015), ("job.submit", 0.001, 0.014),
               ("job.execute", 0.016, 11.9), ("executor.batch", 0.017, 11.89),
               ("executor.stage", 0.017, 0.018), ("executor.dispatch", 2.03, 2.04),
               ("executor.fetch", 2.04, 11.57), ("job.aggregate", 11.9, 11.95)]
# What the parent of PR 37 records for a first search: every span of PR 26
# to PR 36, none with ``cpu_s``, no ``executor.wait``, no ``executor.build``
PARENT = [("client.train", 0.0, 12.0), ("client.submit", 0.0, 0.02), ("job.submit", 0.001, 0.019),
          ("job.expand", 0.005, 0.015), ("client.wait", 0.02, 12.0), ("job.execute", 0.02, 11.95),
          ("executor.batch", 0.03, 11.9), ("executor.load_data", 0.03, 0.53),
          ("executor.split_plan", 0.53, 2.53), ("executor.stage", 2.53, 3.03),
          ("executor.stage", 2.6, 2.9),  # the upload nested in a mesh stage span
          ("executor.compile", 3.03, 5.43), ("executor.dispatch", 5.43, 6.0),
          ("executor.fetch", 6.0, 11.5), ("executor.emit", 11.5, 11.7),
          ("job.aggregate", 11.9, 11.95)]
ON_THE_PARENT = {"first_search_untraced_pct": 100.0 * 0.26 / 12.0,  # 0.02-0.03, 11.7-11.9, 11.95-12.0
                 "first_build_s": 2.4, "first_host_prep_s": 3.0}


def _record(job_id, spans):
    from cs230_distributed_machine_learning_tpu.obs import TRACER
    from cs230_distributed_machine_learning_tpu.obs.tracing import new_trace_id

    tid = new_trace_id()  # a trace a call: the cases share one tracer
    TRACER.bind_job(job_id, tid)
    for i, (name, a, b) in enumerate(spans):
        attrs = {"outcome": "miss"} if spans is PARENT and name == "executor.stage" else {}
        TRACER.record({"trace_id": tid, "span_id": f"{i:08x}", "parent_id": None, "name": name,
                       "start": 1_790_000_000.0 + a, "end": 1_790_000_000.0 + b, "attrs": attrs,
                       "process": "pid:1"})
    return {"searches": [{"job_id": job_id}, {"job_id": "never-traced"}],
            "first": {"job_id": job_id}}


@pytest.mark.parametrize("name", WINDOW_METRICS + FIRST_METRICS)
def test_reader_finds_nothing_where_its_spans_are_missing(name):
    """The parent commit is run with these readers: where the program has
    no ``executor.wait``, no ``executor.build`` and no ``cpu_s`` a reader
    returns nothing and does not raise. The three that need only spans the
    parent has read them there."""
    read = run.load_module(f"layer_metrics/{name}.py").read
    assert read(_record("job-old-37", BEFORE_PR26)) is None
    on_parent = read(_record("job-parent-37", PARENT))
    if name in ON_THE_PARENT:
        assert on_parent == pytest.approx(ON_THE_PARENT[name])
    else:
        assert on_parent is None
    assert read({"searches": [], "first": {"job_id": "never-traced"}}) is None


def test_device_wait_counts_each_blocked_second_once():
    """Waits for the device are summed whole; a dispatch adds what is left
    of its wall after the waits nested in it and its own CPU time (the
    thread blocked inside the enqueue); a wait for the compiler's threads
    is nobody's device wait and is part of the build."""
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid, t = "wa1t000000000037", 1_790_000_000.0
    TRACER.bind_job("job-wait-37", tid)
    for sid, parent, name, a, b, cpu, attrs in [
            ("c0", None, "executor.compile", 0.0, 3.0, 2.9, {"cache": "traced"}),
            ("b0", "c0", "executor.build", 0.1, 2.1, 2.0, {"stage": "cost"}),
            ("b1", "c0", "executor.build", 2.1, 2.9, 0.8, {"stage": "export", "source": "traced"}),
            ("d0", None, "executor.dispatch", 3.0, 13.0, 0.5, {}),
            ("w0", "d0", "executor.wait", 3.1, 4.1, 0.0, {"on": "compile"}),
            ("w1", "d0", "executor.wait", 5.0, 7.0, 0.0, {"on": "backpressure"}),
            ("f0", None, "executor.fetch", 13.0, 20.0, 0.1, {}),
            ("w2", "f0", "executor.wait", 13.0, 19.5, 0.0, {"on": "result"})]:
        TRACER.record({"trace_id": tid, "span_id": sid, "parent_id": parent, "name": name,
                       "start": t + a, "end": t + b, "cpu_s": cpu, "attrs": attrs,
                       "process": "pid:1"})
    ctx = {"searches": [{"job_id": "job-wait-37"}], "first": {"job_id": "job-wait-37"}}

    def read(name):
        return run.load_module(f"layer_metrics/{name}.py").read(ctx)

    # 2.0 + 6.5 of waits, and 10.0 - (1.0 + 2.0) - 0.5 = 6.5 blocked in the enqueue
    assert read("device_wait_ms") == pytest.approx(15000.0)
    assert read("first_device_wait_s") == pytest.approx(15.0)
    assert read("host_enqueue_ms") == pytest.approx(500.0)
    assert read("first_build_s") == pytest.approx(3.0 + 1.0)
    assert read("first_cost_trace_s") == pytest.approx(2.0)
