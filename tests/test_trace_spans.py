"""Real spans on the profiler's clock: one primitive (obs/tracing.span)
with two sinks, spans where the work happens, and a critical path that
tiles a direct (local) job.

The engine's phases used to be synthesized after the batch from its
timers; these tests pin that they are real intervals now, recorded where
the work runs, children of ``executor.batch``, and that the same intervals
reach a ``jax.profiler`` session as ``tpuml.<span name>`` annotations.
"""

import time

import jax
import numpy as np
import pytest
from sklearn.linear_model import LogisticRegression
from sklearn.model_selection import GridSearchCV

from cs230_distributed_machine_learning_tpu import MLTaskManager
from cs230_distributed_machine_learning_tpu.data.datasets import stage_arrays
from cs230_distributed_machine_learning_tpu.obs import (
    TRACER,
    Tracer,
    activate,
    child_span,
    span,
    use_tracer,
)
from cs230_distributed_machine_learning_tpu.obs import tracing

ENGINE = ("executor.load_data", "executor.split_plan", "executor.plan",
          "executor.stage", "executor.compile", "executor.dispatch",
          "executor.fetch", "executor.emit")


def _search():
    return GridSearchCV(
        LogisticRegression(max_iter=400), {"C": [0.01, 0.1, 1.0, 10.0]}, cv=5
    )


def _train(manager):
    status = manager.train(_search(), "iris", show_progress=False)
    assert status["job_status"] == "completed"
    # the job thread records job.execute / job.aggregate just after the
    # finalize that woke the client
    deadline = time.time() + 5
    while time.time() < deadline:
        names = {s["name"] for s in TRACER.spans_for(manager.trace_id)}
        if {"job.execute", "job.aggregate"} <= names:
            break
        time.sleep(0.01)
    return TRACER.spans_for(manager.trace_id)


@pytest.fixture()
def local_search():
    manager = MLTaskManager()
    _train(manager)  # warm: executables built, dataset and folds staged
    return manager, _train(manager)


def _one(spans, name):
    (s,) = [s for s in spans if s["name"] == name]
    return s


def test_engine_spans_are_real_children_of_the_batch(local_search):
    _manager, spans = local_search
    batch = _one(spans, "executor.batch")
    children = [s for s in spans if s["parent_id"] == batch["span_id"]]
    assert {s["name"] for s in children} == set(ENGINE)
    assert not any("synthesized" in s["attrs"] for s in spans)
    children.sort(key=lambda s: s["start"])
    eps = 1e-3  # a child's wall anchor is read after its parent's
    for a, b in zip(children, children[1:]):
        assert a["end"] <= b["start"] + eps, (a["name"], b["name"])
    for s in children:
        assert batch["start"] - eps <= s["start"] <= s["end"] <= batch["end"] + eps
    order = [s["name"] for s in children]
    assert order.index("executor.load_data") < order.index("executor.split_plan")
    assert order.index("executor.split_plan") < order.index("executor.dispatch")
    assert order.index("executor.dispatch") < order.index("executor.fetch")
    assert order[-1] == "executor.emit"
    # attributes the per-layer readers and the docs promise
    assert _one(spans, "executor.split_plan")["attrs"]["n_rows"] == 150
    assert _one(spans, "executor.split_plan")["attrs"]["n_splits"] == 6
    assert 0 <= _one(spans, "executor.split_plan")["attrs"]["fingerprint_s"] < 0.01  # memoised
    assert _one(spans, "executor.plan")["attrs"] == {"engine": "generic", "chunk": 4}
    stages = [s["attrs"] for s in spans if s["name"] == "executor.stage"]
    assert {a["what"] for a in stages} >= {"data", "folds"}
    assert all(a["outcome"] == "hit" and a["bytes"] == 0 for a in stages)  # warm
    assert _one(spans, "executor.compile")["attrs"]["cache"] == "hit"
    assert _one(spans, "executor.dispatch")["attrs"] == {
        "engine": "generic", "chunk": 0, "n_trials": 4, "n_devices": 1, "lanes": 4,
        "lanes_padding": 0}
    assert _one(spans, "executor.fetch")["attrs"]["bytes"] > 0
    assert _one(spans, "executor.fetch")["attrs"]["n_devices"] == 1
    assert _one(spans, "executor.emit")["attrs"]["n_subtasks"] == 4
    assert batch["attrs"]["n_dispatches"] == 1  # the summary stays on the batch


def test_a_cold_search_stages_and_builds_with_outcomes():
    """The first search of a dataset and shape: stage spans say ``miss``
    with the bytes they placed, the compile span says where the executable
    came from."""
    rng = np.random.default_rng(26)
    X = rng.normal(size=(173, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int64)
    stage_arrays("cold26", X, y)
    manager = MLTaskManager()
    status = manager.train(
        GridSearchCV(LogisticRegression(max_iter=50), {"C": [0.5, 2.0]}, cv=3),
        "cold26", show_progress=False,
    )
    assert status["job_status"] == "completed" and not status["job_result"]["failed"]
    spans = TRACER.spans_for(manager.trace_id)
    stages = {s["attrs"]["what"]: s["attrs"] for s in spans if s["name"] == "executor.stage"}
    assert stages["data"]["outcome"] == "miss" and stages["data"]["bytes"] == X.nbytes
    assert stages["folds"]["outcome"] == "miss" and stages["folds"]["bytes"] > 0
    assert _one(spans, "executor.compile")["attrs"]["cache"] in ("traced", "aot")


def test_client_train_covers_the_wait_and_the_submit(local_search):
    _manager, spans = local_search
    train, wait, submit = (_one(spans, n) for n in ("client.train", "client.wait", "client.submit"))
    assert wait["parent_id"] == submit["parent_id"] == train["span_id"]
    assert train["start"] <= submit["start"] <= submit["end"] <= wait["start"] + 1e-3
    assert wait["end"] <= train["end"] + 1e-3
    assert _one(spans, "job.submit")["parent_id"] == submit["span_id"]
    # the wait lasts until the job's last result is in (the job's thread
    # may start its batch before submit has returned to the client)
    batch = _one(spans, "executor.batch")
    assert train["start"] <= batch["start"] and batch["end"] <= wait["end"] + 1e-3
    assert train["attrs"]["job_id"] == wait["attrs"]["job_id"]


def test_critical_path_tiles_a_direct_job(local_search):
    manager, _spans = local_search
    report = manager.critical_path()
    assert sum(s["duration_s"] for s in report["segments"]) == pytest.approx(report["wall_s"])
    assert report["coverage"] >= 0.9, report["totals"]
    assert {"executor.split_plan", "executor.dispatch", "executor.fetch"} <= set(report["totals"])
    assert report["winning_worker"] is None  # nothing was placed: the direct rule
    assert report["dominant"][0].startswith("executor.")


def test_span_budget_and_no_trace_without_an_ambient_one(local_search):
    _manager, spans = local_search
    assert len(spans) <= 64, sorted(s["name"] for s in spans)
    # below the executor a span is a child or nothing: a direct caller of
    # the trial engine has no trace, and must not mint one per call
    from cs230_distributed_machine_learning_tpu.data.datasets import DatasetCache
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials

    kernel = get_kernel("LogisticRegression")
    data = DatasetCache().get("iris", kernel.task)
    plan = build_split_plan(np.asarray(data.y), task=kernel.task, n_folds=3,
                            test_size=0.2, random_state=0)
    private = Tracer(journal=False)
    before = TRACER.traces()
    with use_tracer(private):
        run = run_trials(kernel, data, plan, [{"C": 1.0, "max_iter": 50}])
    assert len(run.trial_metrics) == 1
    assert private.traces() == [] and TRACER.traces() == before
    # with an ambient trace the same call records its spans into it
    with use_tracer(private), activate("feedfacefeedface"):
        run_trials(kernel, data, plan, [{"C": 1.0, "max_iter": 50}])
    names = {s["name"] for s in private.spans_for("feedfacefeedface")}
    assert {"executor.stage", "executor.compile", "executor.dispatch", "executor.fetch"} <= names


def test_child_span_needs_an_ambient_trace():
    t = Tracer(journal=False)
    with use_tracer(t):
        with child_span("orphan", x=1) as sp:
            assert sp.span_id is None
            sp.attrs["y"] = 2  # the inert handle takes attribute writes
        assert t.traces() == []
        with span("parent", trace_id="abcd000000000000") as parent:
            with child_span("kid", x=1) as sp:
                assert sp.parent_id == parent.span_id
    kid = _one(t.spans_for("abcd000000000000"), "kid")
    assert kid["attrs"] == {"x": 1}


# ---------------- waits, build stages and thread CPU (PR 37) ----------------


def _toy_table(n, d=9, n_classes=3, seed=37):
    from cs230_distributed_machine_learning_tpu.models.base import TrialData

    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d, n_classes).astype(np.float32)
    y = np.argmax(X @ w + 0.5 * rng.randn(n, n_classes), axis=1).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=n_classes)


#: engine -> (kernel, rows, trials, the ``on`` of a fresh search's waits,
#: of a warm one's, the chunked plan's steps a dispatch or 0)
ENGINES = {
    "packed": ("LogisticRegression", 700,
               [{"C": c, "tol": 1e-4, "max_iter": 5} for c in (0.1, 1.0, 10.0)],
               {"first_run", "result"}, {"result"}),
    "generic_mesh": ("LogisticRegression", 640,
                     [{"C": c, "tol": 1e-4, "max_iter": 20} for c in (0.1, 0.5, 1.0, 10.0)],
                     {"first_run", "argmax", "result"}, {"argmax", "result"}),
    "chunked": ("GradientBoostingClassifier", 400,
                [{"n_estimators": 12, "max_depth": 3, "random_state": 0}],
                {"compile", "backpressure", "result"}, {"backpressure", "result"}),
}


def _engine_search(engine, monkeypatch):
    """``search()`` runs the engine's toy bucket under an ``executor.batch``
    span of a private tracer and returns (the run, its spans, the chunked
    plan's steps a dispatch); the first call builds its executable."""
    import dataclasses

    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.parallel import trial_map
    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    model, n, params, _fresh, _warm = ENGINES[engine]
    kernel = get_kernel(model)
    data = _toy_table(n, n_classes=2 if engine == "chunked" else 3)
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=3)
    kwargs, seen = {}, {"n_chunks": 0}
    if engine == "packed":
        monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
        resolve = kernel.resolve_static
        monkeypatch.setattr(kernel, "resolve_static",
                            lambda *a: {**resolve(*a), "_method": "nesterov"})
    elif engine == "generic_mesh":
        kwargs["mesh"] = trial_mesh(jax.devices()[:4])
    else:
        monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "1e6")  # several steps a fit
        plan_bucket = trial_map.plan_bucket

        def all_but_two_steps_in_flight(*a, **k):
            bp = plan_bucket(*a, **k)
            seen["n_chunks"] = int(bp.chunk_plan["n_chunks"])
            return dataclasses.replace(bp, steps_ahead=seen["n_chunks"] - 2)

        monkeypatch.setattr(trial_map, "plan_bucket", all_but_two_steps_in_flight)
    monkeypatch.setattr(trial_map, "_compiled_cache", {})
    tracer = Tracer(journal=False)

    def search():
        tid = tracing.new_trace_id()
        with use_tracer(tracer), span("executor.batch", trace_id=tid):
            run = trial_map.run_trials(kernel, data, plan, params, **kwargs)
        return run, tracer.spans_for(tid), seen["n_chunks"]

    return search


def _interval_inside(inner, outer, eps=1e-3):
    return outer["start"] - eps <= inner["start"] <= inner["end"] <= outer["end"] + eps


@pytest.mark.parametrize("warm", [False, True], ids=["fresh", "warm"])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_every_engine_names_its_waits_and_its_build_stages(engine, warm, monkeypatch):
    """Wherever the engine's thread blocks on something else there is one
    ``executor.wait`` (``on`` says on what) inside the fetch or dispatch
    span it happens in, a block a span and never a trial a span; a fresh
    executable's stages are ``executor.build`` children of its
    ``executor.compile``, a hit records none; every span carries the CPU
    time of its thread."""
    search = _engine_search(engine, monkeypatch)
    run, spans, n_chunks = search()
    if warm:
        run, spans, n_chunks = search()
    assert len(run.trial_metrics) == len(ENGINES[engine][2])
    by_id = {s["span_id"]: s for s in spans}
    waits = [s for s in spans if s["name"] == "executor.wait"]
    assert {s["attrs"]["on"] for s in waits} == ENGINES[engine][4 if warm else 3]
    assert len(waits) <= n_chunks + 4, [s["attrs"] for s in waits]
    on = [s["attrs"]["on"] for s in waits]
    # one wait a blocking fetch, and one a step the plan's bound held back
    assert on.count("result") == sum(
        s["name"] == "executor.fetch" and "what" not in s["attrs"] for s in spans)
    assert on.count("backpressure") == (2 if engine == "chunked" else 0)
    for s in waits:
        parent = by_id[s["parent_id"]]
        assert parent["name"] in ("executor.fetch", "executor.dispatch")
        assert _interval_inside(s, parent), (s["attrs"], parent["name"])
    (argmax,) = [s for s in waits if s["attrs"]["on"] == "argmax"] or [None]
    if argmax is not None:
        assert by_id[argmax["parent_id"]]["attrs"]["what"] == "argmax"
    # the build stages: under a fresh executable's compile span only
    builds = [s for s in spans if s["name"] == "executor.build"]
    compiles = [s for s in spans if s["name"] == "executor.compile"]
    assert {c["attrs"]["cache"] for c in compiles} == ({"hit"} if warm else {"traced"})
    assert bool(builds) == (not warm)
    for s in builds:
        parent = by_id[s["parent_id"]]
        assert parent["name"] == "executor.compile" and parent["attrs"]["cache"] != "hit"
        assert _interval_inside(s, parent)
    stages = [s["attrs"]["stage"] for s in builds]
    if not warm:
        assert stages == {
            "packed": ["cost", "pack_spec", "export"],
            "generic_mesh": ["mesh_jit"],
            "chunked": ["export", "export", "pack_spec", "export"],
        }[engine]
        assert all(s["attrs"]["source"] == "traced" for s in builds
                   if s["attrs"]["stage"] == "export")
    # thread CPU: on every span, never more than the wall it was spent in
    for s in spans:
        assert np.isfinite(s["cpu_s"]) and 0 <= s["cpu_s"] <= s["end"] - s["start"] + 1e-3, s


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_valve_off_records_no_wait_and_returns_the_same_results(engine, monkeypatch):
    search = _engine_search(engine, monkeypatch)
    on, spans, _ = search()
    assert any(s["name"] == "executor.wait" for s in spans)
    monkeypatch.setenv("CS230_OBS", "0")
    off, spans, _ = search()
    assert spans == []
    assert len(off.trial_metrics) == len(on.trial_metrics)
    for a, b in zip(on.trial_metrics, off.trial_metrics):
        assert (a["accuracy"], a["cv_scores"]) == (b["accuracy"], b["cv_scores"])


def test_cpu_time_tells_a_span_that_computes_from_one_that_waits():
    """``cpu_s`` is the thread's CPU time inside the span: wall less
    ``cpu_s`` (less the children's wall) is time the thread was blocked."""
    t = Tracer(journal=False)
    with span("waits", trace_id="cpu0000000000000", tracer=t):
        time.sleep(0.05)
    with span("computes", trace_id="cpu0000000000000", tracer=t):
        end = time.thread_time() + 0.05  # of CPU, however loaded the machine is
        while time.thread_time() < end:
            pass
    waits, computes = t.spans_for("cpu0000000000000")
    assert waits["end"] - waits["start"] >= 0.05 and 0 <= waits["cpu_s"] < 0.02
    assert 0.05 <= computes["cpu_s"] <= computes["end"] - computes["start"] + 1e-3


def test_emit_span_says_where_its_time_went(local_search):
    """``executor.emit``'s three shares are attributes, not a span a
    subtask: building the result dicts, the result callback, the metrics
    message and its callback."""
    _manager, spans = local_search
    emit = _one(spans, "executor.emit")
    shares = [emit["attrs"][k] for k in ("build_s", "on_result_s", "on_metrics_s")]
    assert all(np.isfinite(v) and v >= 0 for v in shares)
    assert 0 < sum(shares) <= emit["end"] - emit["start"] + 1e-3
    assert not any(s["parent_id"] == emit["span_id"] for s in spans)


class _CountingAnnotation:
    opened = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        _CountingAnnotation.opened.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


def test_valve_off_touches_neither_sink(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.opened = []
    t = Tracer(journal=False)
    with use_tracer(t):
        with span("on", trace_id="0n00000000000000"):
            pass
    assert _CountingAnnotation.opened == ["tpuml.on"]
    monkeypatch.setenv("CS230_OBS", "0")
    before = TRACER.traces()
    with use_tracer(t):
        with span("off", trace_id="0ff0000000000000"):
            with child_span("off.child"):
                pass
        status = MLTaskManager().train(
            LogisticRegression(max_iter=100), "iris", show_progress=False
        )
    assert status["job_status"] == "completed"
    assert _CountingAnnotation.opened == ["tpuml.on"]
    assert t.traces() == ["0n00000000000000"] and TRACER.traces() == before


def test_duration_follows_perf_counter_not_the_wall(monkeypatch):
    """``start`` is the wall anchor; ``end - start`` is perf_counter's. A
    wall clock that steps backwards inside a span cannot invert it."""
    wall = iter([1000.0, 990.0, 980.0])
    perf = iter([5.0, 5.25])
    monkeypatch.setattr(tracing.time, "time", lambda: next(wall))
    monkeypatch.setattr(tracing.time, "perf_counter", lambda: next(perf))
    t = Tracer(journal=False)
    with span("stepped", trace_id="5te9000000000000", tracer=t):
        pass
    (s,) = t.spans_for("5te9000000000000")
    assert s["start"] == 1000.0 and s["end"] == pytest.approx(1000.25)


def test_profiler_trace_holds_the_programs_spans(tmp_path, local_search):
    """One interval, two sinks: under a ``jax.profiler`` session the host
    plane carries the job's spans as ``tpuml.<name>`` events, the engine's
    inside the client's."""
    manager, _spans = local_search
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        _train(manager)
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "prof").glob("plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tpuml."):
                        events.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    assert {"tpuml." + n for n in ENGINE} <= set(events)
    assert {"tpuml.client.train", "tpuml.client.wait", "tpuml.job.execute",
            "tpuml.executor.batch", "tpuml.job.aggregate"} <= set(events)
    (t0, t1), = events["tpuml.client.train"]
    for name in ("tpuml.executor.split_plan", "tpuml.executor.dispatch", "tpuml.executor.fetch"):
        for s, e in events[name]:
            assert t0 <= s <= e <= t1, name
    (b0, b1), = events["tpuml.executor.batch"]
    (d0, _d1), = events["tpuml.executor.dispatch"]
    (f0, f1), = events["tpuml.executor.fetch"]
    assert b0 <= d0 <= f0 <= f1 <= b1


# ---------------- the journal both sinks of obs write through ----------------


def test_journal_keeps_its_handle_and_every_line_is_readable_at_once(tmp_path, monkeypatch):
    """A line used to cost makedirs + getsize + open + close (0.6 ms on the
    benchmark machine's filesystem, PERF.md PR 26); the handle now stays
    open, and a reader still sees each line the moment it is written."""
    monkeypatch.setenv("CS230_JOURNAL_DIR", str(tmp_path / "j"))
    opened = []
    real_open = open
    monkeypatch.setattr("builtins.open", lambda *a, **k: opened.append(a[0]) or real_open(*a, **k))
    for i in range(5):
        tracing.journal_append("lines.jsonl", {"i": i})
        assert len((tmp_path / "j" / "lines.jsonl").read_text().splitlines()) == i + 1
    assert opened.count(str(tmp_path / "j" / "lines.jsonl")) == 1


def test_journal_reopens_a_file_rotated_or_removed_by_someone_else(tmp_path, monkeypatch):
    monkeypatch.setenv("CS230_JOURNAL_DIR", str(tmp_path / "j"))
    monkeypatch.setattr(tracing, "_JOURNAL_RECHECK_S", 0.0)
    path = tmp_path / "j" / "shared.jsonl"
    tracing.journal_append("shared.jsonl", {"n": 1})
    path.rename(tmp_path / "j" / "shared.jsonl.1")  # another process rotated it
    tracing.journal_append("shared.jsonl", {"n": 2})
    path.unlink()  # ... and an operator removed it
    tracing.journal_append("shared.jsonl", {"n": 3})
    assert path.read_text() == '{"n": 3}\n'
    assert (tmp_path / "j" / "shared.jsonl.1").read_text() == '{"n": 1}\n'
    # another writer's lines count toward the size that rotates the file
    monkeypatch.setenv("CS230_JOURNAL_MAX_MB", "0.0001")  # 100 bytes
    with open(path, "a") as other:
        other.write("x" * 200 + "\n")
    tracing.journal_append("shared.jsonl", {"n": 4})
    assert path.read_text() == '{"n": 4}\n'


def test_journal_holds_a_bounded_number_of_handles(tmp_path, monkeypatch):
    for i in range(tracing._JOURNAL_MAX_OPEN + 3):
        monkeypatch.setenv("CS230_JOURNAL_DIR", str(tmp_path / f"root{i}"))
        tracing.journal_append("spans.jsonl", {"i": i})
    assert len(tracing._JOURNAL_OPEN) == tracing._JOURNAL_MAX_OPEN
    assert str(tmp_path / "root0" / "spans.jsonl") not in tracing._JOURNAL_OPEN
    assert (tmp_path / "root0" / "spans.jsonl").read_text() == '{"i": 0}\n'
