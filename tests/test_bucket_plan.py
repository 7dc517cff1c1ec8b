"""The trial engine's bucket plan and executable builder (trial_map.py).

``plan_bucket`` is where an engine is chosen: host / streamed / chunked /
packed / generic, with its placement and chunk geometry, from host-side
facts only. These cases pin the choice for the benchmark's cells and for
every predicate, without staging, building or dispatching anything.
"""

import numpy as np
import pytest

import jax

from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import trial_map
from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

#: the benchmark's LogReg configuration (logreg_rows5m: 5M x 54, 7 classes)
ROWS_5M = (5_000_000, 54, 7)


def _mesh(kind):
    if kind is None:
        return None
    devices = jax.devices()[:4]
    return trial_mesh(devices, data_parallel=2 if kind == "2d" else 1)


def _plan(name, shape, n_trials, n_splits, params=None, **kw):
    """plan_bucket for ``n_trials`` trials of one static config, on a
    matrix of ``shape`` that is never materialised."""
    n, d, c = shape
    kernel = get_kernel(name)
    static_key, hyper = kernel.canonicalize(params or {})
    static = trial_map._resolved_static(
        kernel, static_key, n, d, c, kw.get("scoring")
    )
    host_X = np.broadcast_to(np.float32(0), (n, d))
    plan = trial_map.plan_bucket(
        kernel, static, [hyper] * n_trials, host_X, n=n, d=d, n_classes=c,
        n_splits=n_splits, **kw,
    )
    return kernel, plan


@pytest.fixture
def no_device_work(monkeypatch):
    """The plan must not stage, build or dispatch."""

    def refuse(*a, **k):
        raise AssertionError("plan_bucket touched the device path")

    for name in ("_staged_device", "_staged_mesh", "_build_executable",
                 "_dispatch_span", "_fetch_result"):
        monkeypatch.setattr(trial_map, name, refuse)
    # the packed LogReg fit is applicable on the CPU under the interpreter
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    return monkeypatch


#: (case, kernel, shape, trials, splits, mesh, extra kwargs, expected fields)
_CASES = [
    # the cell logreg_rows5m.rs128: one weight block of 128, one dispatch
    ("logreg_one_device_128", "LogisticRegression", ROWS_5M, 128, 6, None, {},
     dict(engine="packed", kind="device", chunk=128, block=128, blocks=1,
          dev_chunk=128, slab_lanes=768, slab_pad_lanes=0)),
    # 64 trials at six splits: a block of 64, its slabs three whole vregs
    ("logreg_one_device_64", "LogisticRegression", ROWS_5M, 64, 6, None, {},
     dict(engine="packed", kind="device", chunk=64, block=64, blocks=1,
          dev_chunk=64, slab_lanes=384, slab_pad_lanes=0)),
    # the cell logreg_rows5m_mesh4.rs64c: a share of 16 in a block of 16,
    # each class slab's 96 lanes padded to one vreg
    ("logreg_mesh4_64", "LogisticRegression", ROWS_5M, 64, 6, "1d", {},
     dict(engine="packed", kind="mesh_1d", chunk=64, block=16, blocks=1,
          dev_chunk=16, slab_lanes=128, slab_pad_lanes=32)),
    # 16 trials on one device ride the same block of 16
    ("logreg_one_device_16", "LogisticRegression", ROWS_5M, 16, 6, None, {},
     dict(engine="packed", kind="device", chunk=16, block=16, blocks=1,
          dev_chunk=16, slab_lanes=128, slab_pad_lanes=32)),
    # four splits: 16 and 32 both make a slab of one vreg, the narrower runs
    ("logreg_mesh4_64_four_splits", "LogisticRegression", ROWS_5M, 64, 4, "1d",
     {}, dict(engine="packed", kind="mesh_1d", chunk=64, block=16, blocks=1,
              dev_chunk=16, slab_lanes=128, slab_pad_lanes=64)),
    # more than a block a device: whole blocks, capped by the kernel
    ("logreg_one_device_300", "LogisticRegression", ROWS_5M, 300, 6, None, {},
     dict(engine="packed", kind="device", chunk=384, block=128, blocks=3,
          dev_chunk=384, slab_lanes=768, slab_pad_lanes=0)),
    # the cell mlp_mnist.rs64: lanes pack per (trial, split), a block is one
    # trial and there is no slab to report
    ("mlp_one_device_64", "MLPClassifier", (60_000, 784, 10), 64, 6, None, {},
     dict(engine="packed", kind="device", chunk=64, block=1, blocks=64,
          dev_chunk=64, slab_lanes=None, slab_pad_lanes=None)),
    # XLA partitions a (trials, data) mesh: no fused kernel there
    ("logreg_2d_mesh", "LogisticRegression", (10_240, 54, 7), 8, 4, "2d", {},
     dict(engine="generic", kind="mesh_2d", chunk=8, block=None,
          slab_lanes=None)),
    # fused paths score by the default metric only
    ("logreg_custom_scorer", "LogisticRegression", (10_240, 54, 7), 8, 4, None,
     {"scoring": "f1_macro"},
     dict(engine="generic", kind="device", chunk=8, block=None)),
    # no build_batched_fn: the vmapped fit, capped by max_trials_per_batch
    ("ridge_generic_capped", "Ridge", (200, 6, 0), 40, 4, None,
     {"max_trials_per_batch": 16},
     dict(engine="generic", kind="device", chunk=16, split_width=None)),
    # ... and on a 1-D mesh the chunk is a multiple of its devices
    ("ridge_generic_mesh", "Ridge", (200, 6, 0), 6, 4, "1d", {},
     dict(engine="generic", kind="mesh_1d", chunk=8, split_width=None)),
    # a closed-form family: one lane
    ("gnb_generic", "GaussianNB", (150, 4, 3), 1, 4, None, {},
     dict(engine="generic", kind="device", chunk=1)),
]


@pytest.mark.parametrize(
    "name,shape,n_trials,n_splits,mesh_kind,kw,want",
    [c[1:] for c in _CASES], ids=[c[0] for c in _CASES],
)
def test_plan_engine_placement_geometry(no_device_work, name, shape, n_trials,
                                        n_splits, mesh_kind, kw, want):
    kernel, plan = _plan(name, shape, n_trials, n_splits,
                         mesh=_mesh(mesh_kind), **kw)
    want = dict(want)
    assert plan.placement.kind == want.pop("kind")
    for field, value in want.items():
        assert getattr(plan, field) == value, (field, plan)
    assert (plan.placement.mesh is None) == (mesh_kind is None)
    assert plan.chunk % plan.placement.n_dev == 0
    if plan.engine == "generic":
        n, d, _ = shape
        assert plan.mem_cap == trial_map._memory_chunk_cap(
            kernel, n, d, plan.static, n_splits, plan.placement.n_dev
        )
        assert plan.chunk <= max(plan.mem_cap, plan.placement.n_dev)
    if "scoring" in kw:
        assert plan.static["_scoring"] == kw["scoring"]


def test_plan_forest_takes_the_chunked_protocol(no_device_work):
    """A fit longer than one dispatch's MAC budget is cut into chunks: the
    kernel's own plan rides the record, the trial chunk is bounded by the
    state and the working set."""
    no_device_work.setenv("CS230_TREE_CHUNK_MACS", "1e6")
    _, plan = _plan("RandomForestClassifier", (2_000, 10, 3), 5, 4,
                    params={"n_estimators": 16, "max_depth": 4})
    assert plan.engine == "chunked" and plan.placement.kind == "device"
    assert plan.chunk_plan["n_chunks"] > 1
    assert plan.chunk == 5 and plan.mem_cap >= 5 and plan.split_width is None
    # ... with the trial axis over every device of a mesh
    _, plan = _plan("RandomForestClassifier", (2_000, 10, 3), 5, 4,
                    params={"n_estimators": 16, "max_depth": 4},
                    mesh=_mesh("1d"))
    assert plan.engine == "chunked" and plan.placement.kind == "mesh_1d"
    assert plan.chunk == 8


#: one v5e chip's ``bytes_limit`` in MB: the lane budget is half of it
V5E_MB = 16_900.0
#: the benchmark's boosting configuration (gbt_higgs: 1M x 28, binary, depth 8)
HIGGS_1M = (1_000_000, 28, 2)

#: (case, shape, depth, trials, expected chunk, split width, memory cap, steps ahead)
_BOOST_CASES = [
    # the cell gbt_higgs.rs8: eight traced trials are ONE bucket and ride one
    # dispatch with all six folds, 48 lanes (64.5 MB a lane by the corrected
    # estimate; the old one said 2 033 MB: one trial, fold groups of 4 and 2);
    # 0.38 GB of carried scores a step, so a quarter of the chip holds the
    # step being read and ten enqueued ahead
    ("higgs_cell_8_trials", HIGGS_1M, 8, 8, 8, None, 21, 10),
    # a bucket past the cap is cut by it, not by the fold stack; 126 lanes
    # carry 1.0 GB a step: three ahead
    ("higgs_cell_30_trials", HIGGS_1M, 8, 30, 21, None, 21, 3),
    # depth 10 passes the lookup forms (gathers, segment sums) at twice the rows
    ("two_million_rows_depth_10", (2_000_000, 28, 2), 10, 8, 4, None, 4, 10),
    # depth 14 at four times the rows: one trial's six folds no longer fit
    # half a chip, so the folds go in groups of five
    ("four_million_rows_depth_14", (4_000_000, 28, 2), 14, 8, 1, 5, 1, 21),
]


@pytest.mark.parametrize("shape,depth,n_trials,chunk,split_width,mem_cap,steps_ahead",
                         [c[1:] for c in _BOOST_CASES], ids=[c[0] for c in _BOOST_CASES])
def test_plan_boosting_bucket_batches_what_the_chip_holds(
        no_device_work, shape, depth, n_trials, chunk, split_width, mem_cap, steps_ahead):
    """The chunked engine's trial batching for a boosted bucket at a chip's
    memory: learning_rate and subsample are traced, so every trial of the
    search shares one static key and one plan. The states in flight (one a
    step enqueued ahead, and the one being read) stay inside a quarter of
    the chip whatever the stage count."""
    no_device_work.setattr(trial_map._backend, "device_memory_mb", lambda: V5E_MB)
    no_device_work.setattr(trial_map._backend, "on_tpu", lambda: True)  # the TPU compiler's lane estimate
    kernel = get_kernel("GradientBoostingClassifier")
    params = {"n_estimators": 16, "max_depth": depth, "random_state": 0}
    keys = {kernel.canonicalize({**params, "learning_rate": lr, "subsample": ss})[0]
            for lr, ss in ((0.05, 0.5), (0.1, 1.0), (0.5, 0.75))}
    assert len(keys) == 1  # one bucket whatever the draws
    _, plan = _plan("GradientBoostingClassifier", shape, n_trials, 6, params=params)
    assert plan.engine == "chunked" and plan.placement.kind == "device"
    assert plan.hyper_names == ("learning_rate", "subsample")
    assert (plan.chunk, plan.split_width, plan.mem_cap) == (chunk, split_width, mem_cap)
    assert plan.chunk_plan["trees_per_chunk"] * plan.chunk_plan["n_chunks"] >= 16
    lane_mb = kernel.memory_estimate_mb(shape[0], shape[1], plan.static)
    assert plan.chunk * (split_width or 6) * lane_mb <= 0.5 * V5E_MB or plan.chunk == 1
    assert plan.steps_ahead == steps_ahead
    state_mb = plan.chunk * 6 * shape[0] * 2 * 4 / 1e6  # F [trials, folds, n, 2] float32
    assert (plan.steps_ahead + 1) * state_mb <= 0.25 * V5E_MB


def test_lane_estimate_off_a_tpu_still_counts_the_routing_forms(no_device_work):
    """Only the TPU compiler was read to fuse the complete builder's [n, m]
    routing and leaf forms away; any other backend keeps the old term."""
    kernel = get_kernel("GradientBoostingClassifier")
    static = {"_depth": 8, "_n_bins": 128, "_n_classes": 2}
    no_device_work.setattr(trial_map._backend, "on_tpu", lambda: True)
    on_tpu = kernel.memory_estimate_mb(*HIGGS_1M[:2], static)
    no_device_work.setattr(trial_map._backend, "on_tpu", lambda: False)
    elsewhere = kernel.memory_estimate_mb(*HIGGS_1M[:2], static)
    assert on_tpu == pytest.approx(64.5, abs=0.1)
    assert elsewhere == pytest.approx(on_tpu + (6 * 128 + 4 * 256) * 1e6 / 1e6, abs=0.1)


def test_plan_tiny_bucket_off_the_cpu_runs_on_the_host(no_device_work):
    """On an accelerator backend an iris-sized bucket is not worth one
    device round trip; on the CPU backend there is no host to prefer."""
    _, plan = _plan("LogisticRegression", (150, 4, 3), 3, 4)
    assert plan.engine == "generic"
    no_device_work.setattr(trial_map._backend, "on_cpu", lambda: False)
    kernel, plan = _plan("LogisticRegression", (150, 4, 3), 3, 4)
    assert plan.engine == "host" and plan.placement.kind == "host"
    assert plan.placement.mesh is None and plan.chunk == 3
    assert (kernel.macs_estimate(150, 4, plan.static) * 4 * 3
            <= trial_map._HOST_EXEC_MACS)
    # a mesh is the caller's word that the accelerators are wanted
    no_device_work.setattr(trial_map._backend, "device_memory_mb", lambda: 8e3)
    _, plan = _plan("LogisticRegression", (150, 4, 3), 3, 4, mesh=_mesh("1d"))
    assert plan.engine != "host"


def test_plan_streams_a_matrix_past_the_stage_budget(no_device_work):
    """A matrix over half the stage budget never stages whole, for a
    kernel with a streaming driver, the default scorer and one device."""
    shape = (4_000, 128, 7)  # (d + 1) * c > 512: the Nesterov solver
    _, plan = _plan("LogisticRegression", shape, 4, 4)
    assert plan.engine == "packed"
    no_device_work.setenv("CS230_STAGE_CACHE_MB", "1")
    _, plan = _plan("LogisticRegression", shape, 4, 4,
                    max_trials_per_batch=3)
    assert plan.engine == "streamed" and plan.placement.kind == "device"
    assert plan.chunk == 3
    _, plan = _plan("LogisticRegression", shape, 4, 4, scoring="f1_macro")
    assert plan.engine == "generic"
    no_device_work.setenv("CS230_STREAM", "0")
    _, plan = _plan("LogisticRegression", shape, 4, 4)
    assert plan.engine == "packed"


def test_plan_splits_the_folds_when_one_trial_passes_half_of_hbm(no_device_work):
    """Six folds of one trial over a budget that holds two: fold groups of
    two, at one trial a device."""
    shape = (200, 6, 0)
    kernel = get_kernel("Ridge")
    per_split = max(kernel.memory_estimate_mb(200, 6, {}), 0.5)
    # half of this "device" holds 2.5 splits of one trial
    no_device_work.setattr(trial_map._backend, "device_memory_mb",
                           lambda: 5.0 * per_split)
    _, plan = _plan("Ridge", shape, 3, 6)
    assert plan.engine == "generic"
    assert plan.mem_cap == 1 and plan.chunk == 1 and plan.split_width == 2
    # a budget that holds the stack: all folds in one dispatch
    no_device_work.setattr(trial_map._backend, "device_memory_mb",
                           lambda: 12.0 * per_split)
    _, plan = _plan("Ridge", shape, 3, 6)
    assert plan.chunk == 1 and plan.split_width is None


def test_one_builder_builds_all_four_tags(monkeypatch):
    """host, batched, generic and chunked executables all come from
    ``_build_executable``; a one-device program is packed and priced, a
    mesh program hands back the per-leaf dict, unpriced, ``traced``."""
    from cs230_distributed_machine_learning_tpu.obs import TRACER, span

    import threading

    built = []
    real = trial_map._build_executable
    here = threading.get_ident()

    def recording(key, make_parts):
        entry, fresh = real(key, make_parts)
        # an executor thread another test file left running in this worker
        # may build too (seen once under xdist: an iris job of its own)
        if threading.get_ident() == here:
            built.append((key, entry, fresh))
        return entry, fresh

    monkeypatch.setattr(trial_map, "_build_executable", recording)
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "1e6")
    saved = dict(trial_map._compiled_cache)
    trial_map._compiled_cache.clear()
    try:
        rng = np.random.RandomState(0)
        X = rng.randn(300, 128).astype(np.float32)
        y = rng.randint(0, 7, 300).astype(np.int32)
        wide = TrialData(X=X, y=y, n_classes=7)
        wplan = build_split_plan(y, task="classification", n_folds=2)
        small = TrialData(X=X[:, :5], y=(y > 3).astype(np.int32), n_classes=2)
        splan = build_split_plan(np.asarray(small.y), task="classification",
                                 n_folds=2)
        logreg = get_kernel("LogisticRegression")
        with span("test.builder") as root:
            # batched: the packed fit ((d + 1) * c > 512 -> Nesterov)
            trial_map.run_trials(logreg, wide, wplan,
                                 [{"C": 1.0, "max_iter": 3}])
            # generic, on one device and on a mesh
            trial_map.run_trials(get_kernel("Ridge"), small, splan,
                                 [{"alpha": 1.0}])
            trial_map.run_trials(logreg, small, splan, [{"C": 1.0}],
                                 mesh=_mesh("1d"))
            # chunked
            trial_map.run_trials(
                get_kernel("RandomForestClassifier"), small, splan,
                [{"n_estimators": 4, "max_depth": 2, "random_state": 0}])
            # host: an accelerator process's tiny bucket
            monkeypatch.setattr(trial_map._backend, "on_cpu", lambda: False)
            trial_map.run_trials(logreg, small, splan, [{"C": 0.5}])
    finally:
        trial_map._compiled_cache.clear()
        trial_map._compiled_cache.update(saved)

    assert [k[0] for k, _, _ in built] == [
        "batched", "generic", "generic", "chunked", "host"]
    assert all(fresh for _, _, fresh in built)
    by_tag = {}
    for key, entry, _ in built:
        by_tag.setdefault(key[0], []).append(entry)
    for tag in ("batched", "host"):
        ((fn, spec, cost),) = by_tag[tag][0]
        assert spec is not None and cost is not None, tag
    ((_, spec, cost),), ((_, mesh_spec, mesh_cost),) = by_tag["generic"]
    assert spec is not None and cost is not None
    assert mesh_spec is None and mesh_cost is None
    # chunked: init and step keep their state on the device, eval packs
    init, step, ev = by_tag["chunked"][0]
    assert init[1] is None and step[1] is None and ev[1] is not None
    assert init[2] is None and step[2] is None and ev[2] is None
    compiles = [s for s in TRACER.spans_for(root.trace_id)
                if s["name"] == "executor.compile"]
    assert len(compiles) == 5
    assert {s["attrs"]["cache"] for s in compiles} == {"traced"}


# ---- the exact-dual SVC's training rows (the cell svc_mnist30k.rs4svc) ----

#: the cell's table: 30 000 x 784, 10 classes, a holdout of 0.2 and five folds
SVC_CELL = (30_000, 784, 10)


def _svc_cell_plan():
    n = SVC_CELL[0]
    return build_split_plan(np.arange(n) % SVC_CELL[2], task="classification",
                            n_folds=5, test_size=0.2, random_state=42)


@pytest.mark.parametrize("device_mb", [16.0e3, 16.9e3])
def test_svc_bucket_carries_its_plans_fit_rows_at_the_cells_geometry(
        no_device_work, device_mb):
    """Every split of the cell trains on 24 000 rows: the bucket's static
    says so, and the plan keeps one trial and one split a dispatch."""
    n, d, c = SVC_CELL
    split_plan = _svc_cell_plan()
    assert split_plan.fit_rows == 24_000
    kernel = get_kernel("SVC")
    static_key, hyper = kernel.canonicalize({"kernel": "rbf", "gamma": "scale", "C": 1.0})
    static = trial_map._resolved_static(kernel, static_key, n, d, c, None, split_plan)
    assert static == {**trial_map._resolved_static(kernel, static_key, n, d, c),
                      "_fit_rows": 24_000}
    assert kernel.memory_estimate_mb(n, d, static) < kernel.memory_estimate_mb(
        n, d, {k: v for k, v in static.items() if k != "_fit_rows"})
    no_device_work.setattr(trial_map._backend, "device_memory_mb", lambda: device_mb)
    plan = trial_map.plan_bucket(
        kernel, static, [hyper] * 4, np.broadcast_to(np.float32(0), (n, d)),
        n=n, d=d, n_classes=c, n_splits=split_plan.n_splits)
    assert (plan.engine, plan.chunk, plan.split_width) == ("generic", 1, 1)
    assert plan.static["_fit_rows"] == 24_000


@pytest.mark.parametrize("name,params", [
    ("LogisticRegression", {"C": 1.0}),
    ("MLPClassifier", {"hidden_layer_sizes": [8]}),
    ("RandomForestClassifier", {"n_estimators": 4}),
    ("GradientBoostingClassifier", {"n_estimators": 4}),
    ("SVR", {"C": 1.0}),
])
def test_other_families_statics_and_keys_do_not_see_the_plan(name, params):
    """Only a kernel that asks for the plan's fit rows gets them: every other
    family's static and executable key are what they were without it."""
    rng = np.random.RandomState(0)
    X = rng.randn(300, 6).astype(np.float32)
    y = rng.randint(0, 3, 300)
    kernel = get_kernel(name)
    c = 3 if kernel.task == "classification" else 0
    split_plan = build_split_plan(y if c else X[:, 0], task=kernel.task,
                                  n_folds=5, test_size=0.2, random_state=42)
    assert split_plan.fit_rows < 300
    static_key, hyper = kernel.canonicalize(params)
    without = trial_map._resolved_static(kernel, static_key, 300, 6, c)
    with_plan = trial_map._resolved_static(kernel, static_key, 300, 6, c, None, split_plan)
    assert with_plan == without and "_fit_rows" not in with_plan
    names = sorted(hyper)
    assert (trial_map._aot_key(kernel, with_plan, X, c, 6, 8, names)
            == trial_map._aot_key(kernel, without, X, c, 6, 8, names))


def test_svc_prewarm_builds_the_executable_its_search_runs():
    """A ``warm_only`` run of an SVC bucket reaches the key, ``_fit_rows``
    included, that the search after it hits."""
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    rng = np.random.RandomState(1)
    X = rng.randn(120, 4).astype(np.float32)
    y = rng.randint(0, 3, 120).astype(np.int32)
    data = TrialData(X=X, y=y, n_classes=3)
    split_plan = build_split_plan(y, task="classification", n_folds=2,
                                  test_size=0.2, random_state=42)
    params = [{"kernel": "rbf", "gamma": "scale", "C": C} for C in (0.5, 5.0)]
    kernel = get_kernel("SVC")
    saved = dict(trial_map._compiled_cache)
    trial_map._compiled_cache.clear()
    try:
        warm = trial_map.run_trials(kernel, data, split_plan, params, warm_only=True)
        assert warm.n_dispatches == 0 and not warm.trial_metrics
        (key,) = trial_map._compiled_cache
        assert ("_fit_rows", str(split_plan.fit_rows)) in key[2]
        hits = REGISTRY.counter("tpuml_executable_cache_hits_total").value()
        misses = REGISTRY.counter("tpuml_executable_cache_misses_total").value()
        out = trial_map.run_trials(kernel, data, split_plan, params)
        assert REGISTRY.counter("tpuml_executable_cache_hits_total").value() > hits
        assert REGISTRY.counter("tpuml_executable_cache_misses_total").value() == misses
        assert list(trial_map._compiled_cache) == [key]
        assert all(0.0 <= m["accuracy"] <= 1.0 for m in out.trial_metrics)
    finally:
        trial_map._compiled_cache.clear()
        trial_map._compiled_cache.update(saved)
