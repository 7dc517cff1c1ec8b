"""The forest search against its plain reference (PR 32).

The cell ``rf_covertype.rs4`` of the benchmark guards the grow-to-purity
``RandomForestClassifier`` through the chunked engine. Here, at a size the
CPU compiles in under a minute and with the deep path forced
(``CS230_TREE_DEEP_N``, as ``tests/test_trees.py`` does): the system through
``MLTaskManager.train`` against ``perfbench/references/
RandomForestClassifier.py`` on seeded data, each control of the cell's
limits reading not correct, the spans a chunked bucket leaves, and the
bucket plan of the cell's four trials at its real shape (no device).
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.obs import REGISTRY, TRACER
from cs230_distributed_machine_learning_tpu.parallel import trial_map

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "rf_covertype.rs4"
#: the toy size: 600 rows (the 64-wide arena, 18 levels) of 4 continuous and
#: 2 + 8 one-hot columns (the CPU's scatter form unrolls a segment-sum a
#: feature a level, so the cell's 54 columns compile for minutes), 2 trees,
#: 3 split lanes, and the two max_features settings as two buckets
ROWS, TREES, CV = 600, 2, 2
COLUMNS = {"n_features": 14, "n_continuous": 4, "onehot_blocks": [2, 8]}
#: limits at the toy size: one held-out row of the 120 of split 0 is 0.0083
TOY_LIMITS = {"score_gap_max": 0.009, "score_gap_mean": 0.003}


def _load_run():
    if "perfbench_run" in sys.modules:
        return sys.modules["perfbench_run"]
    spec = importlib.util.spec_from_file_location("perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["perfbench_run"] = mod
    spec.loader.exec_module(mod)
    return mod


def _toy_cell(run):
    cell = run.load_cell(CELL)
    cell["config"]["dataset"].update(n_samples=ROWS, **COLUMNS)
    cell["config"]["estimator"]["params"]["n_estimators"] = TREES
    cell["config"]["limits"].update(TOY_LIMITS)
    cell["traffic"].update(n_iter=2, cv=CV, check_trials=2)
    cell["traffic"]["param_distributions"] = {"max_features": ["sqrt", "log2"],
                                              "min_samples_leaf": [1]}
    return cell


@pytest.fixture(scope="module")
def toy():
    """Two searches of the toy cell through the system's normal entry (the
    first compiles its two buckets, the second is warm) and the comparison
    of a run on what they returned."""
    import jax

    run = _load_run()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CS230_TREE_DEEP_N", "200")
        mp.setenv("CS230_TREE_CHUNK_MACS", "1e7")  # two chunks of one tree
        cell, seed = _toy_cell(run), 2**31 + 32
        X, y = run.make_dataset(cell, seed)
        manager, _coordinator = run.build_system(cell, X, y, jax.devices()[:1])
        search = run.build_search(cell, seed)
        levels0 = {r: REGISTRY.counter("tpuml_tree_levels_total").value(route=r)
                   for r in ("pallas", "matmul", "scatter")}
        searches = [run.train_once(manager, search, cell) for _ in range(2)]
        levels1 = {r: REGISTRY.counter("tpuml_tree_levels_total").value(route=r)
                   for r in levels0}
        compare = run.load_module("lib/compare.py")
        family = run.load_module("references/RandomForestClassifier.py")
        combos = run.search_kind(cell).expected(cell["traffic"], seed)
        numbers, detail = compare.compare(cell, combos, seed, X, y,
                                          [s["status"] for s in searches], family.reference)
        numbers["failed_trials"] = float(sum(
            compare.count_failed(s["status"], 2) for s in searches))
        spans = [TRACER.spans_for(TRACER.trace_for_job(s["job_id"])) for s in searches]
        yield {"run": run, "cell": cell, "X": X, "y": y, "searches": searches, "spans": spans,
               "compare": compare, "family": family, "numbers": numbers, "detail": detail,
               "levels": {r: levels1[r] - levels0[r] for r in levels0}}


def test_the_system_agrees_with_the_reference(toy):
    correct, table = toy["compare"].judge(toy["numbers"], toy["cell"]["config"]["limits"])
    assert correct, table
    # integer histograms and the same keys: on one backend the trees are the same trees
    assert toy["numbers"]["score_gap_max"] == 0.0
    ref = toy["detail"]["ref"]
    assert ref.shape == (2, CV + 1) and 0.3 < ref.min() and ref.max() < 1.0


@pytest.mark.parametrize("control", [{"precision": "float8_e4m3fn"},
                                     {"fault": "no_feature_subsets"}, {"fault": "half_trees"}])
def test_each_control_reads_not_correct(toy, control):
    """The reference with a narrow accumulator or a known fault, put in the
    program's place and read by the comparison of a run, fails a limit."""
    probe = toy["run"].load_module("tools/probe_limits.py")
    detail, first = toy["detail"], toy["searches"][0]["status"]
    reference = toy["family"].reference
    cell, seed = toy["cell"], 2**31 + 32
    combos = toy["run"].search_kind(cell).expected(cell["traffic"], seed)

    def read(**kw):
        out = reference(toy["X"], toy["y"], 7, detail["params"], detail["splits"], **kw)
        status = probe.in_the_programs_place(first, out, detail, 1)
        numbers, _ = toy["compare"].compare(cell, combos, seed, toy["X"], toy["y"], [status],
                                            lambda *a, **k: {"score": detail["ref"]})
        numbers["failed_trials"] = 0.0
        return toy["compare"].judge(numbers, cell["config"]["limits"])

    correct, table = read(**control)
    assert not correct, table
    assert read()[0]  # the reference as it is, through the same path: correct


def test_gains_compare_on_twelve_bits_and_noise_is_no_gain():
    """What keeps the chip's trees the CPU's (PR 32): one exact gain reached
    through different roundings compares equal, and a pure node's float
    residue, which the chip's division leaves and the CPU's does not, is
    no gain at all."""
    import jax.numpy as jnp

    from cs230_distributed_machine_learning_tpu.ops import trees as ops_trees

    third = np.float32(4.0) / np.float32(3.0)
    near = np.nextafter(third, np.float32(2.0), dtype=np.float32)
    ranked = np.asarray(ops_trees._rank_gain(jnp.asarray([third, near, -np.inf, 0.0], jnp.float32)))
    assert ranked[0] == ranked[1] and abs(ranked[0] - third) < third * 2.0 ** -11
    assert ranked[2] == -np.inf and ranked[3] == 0.0
    family = _load_run().load_module("references/RandomForestClassifier.py")
    assert np.array_equal(family._rank(np.asarray([third, near, -np.inf, 0.0], np.float32)), ranked)
    # a pure node of 999 rows, 2 features x 4 bins: every split's true gain is 0
    H = np.zeros((1, 2, 4, 3), np.float32)
    H[0, :, :, 0] = [[500, 300, 100, 99], [1, 2, 3, 993]]
    H[0, :, :, 2] = H[0, :, :, 0]
    floor = ops_trees.GAIN_NOISE
    g = np.asarray(ops_trees._split_gain(jnp.asarray(H), 2, 4, 1.0, floor))
    assert np.array_equal(g[0, :, :3], np.zeros((2, 3), np.float32)) and np.all(g[0, :, 3] == -np.inf)
    # the residue the chip leaves (a few ulps of the parent's score) is none either
    noisy = ops_trees._split_gain(jnp.asarray(H), 2, 4, 1.0) + np.float32(999 * 4e-7)
    assert float(jnp.max(jnp.where(noisy > floor * 999.0, noisy, 0.0))) == 0.0
    # and a real split of the same node (one row of another class) is far above it
    H[0, 0, 0, :] = [499, 1, 500]
    H[0, 1, 0, :] = [0, 1, 1]
    g = np.asarray(ops_trees._split_gain(jnp.asarray(H), 2, 4, 1.0, floor))
    assert g[0, 1, 0] > 1.9 and family.GAIN_NOISE == np.float32(floor)


def test_a_fresh_chunked_bucket_compiles_ahead_and_dispatches_last(toy):
    """Off a mesh every chunked bucket is built before any is dispatched,
    and a fresh bucket's programs are compiled on worker threads meanwhile:
    the cache holds the compiled programs, not the jitted ones."""
    import jax

    names = [s["name"] for s in sorted(toy["spans"][0], key=lambda s: s["start"])
             if s["name"] in ("executor.compile", "executor.dispatch")]
    assert names == ["executor.compile"] * 2 + ["executor.dispatch"] * 2
    entries = [v for k, v in trial_map._compiled_cache.items() if k[0] == "chunked"]
    assert len(entries) >= 2
    for entry in entries:
        assert len(entry) == 3 and all(isinstance(fn, jax.stages.Compiled) for fn, _, _ in entry)


def test_a_chunked_bucket_says_what_it_did(toy):
    for spans, outcome in zip(toy["spans"], ("miss", "hit")):
        prepare = [s["attrs"] for s in spans if s["name"] == "executor.prepare"]
        assert [p["outcome"] for p in prepare] == [outcome] * 2  # a bucket each
        assert all((p["bytes"] > 0) == (outcome == "miss") for p in prepare)
        dispatch = [s["attrs"] for s in spans if s["name"] == "executor.dispatch"]
        assert len(dispatch) == 2
        for d in dispatch:
            assert d["engine"] == "chunked" and d["n_trials"] == 1 and d["lanes"] == 1
            assert d["n_chunks"] == 2 and d["trees_per_chunk"] == 1
            assert d["split_lanes"] == CV + 1
            # init + two steps + eval, and the curve's one sampled eval
            assert d["dispatches"] in (4, 5)
            assert d["levels"] == 18 and d["arena_width"] == 64
            assert d["hist_route"] == "scatter" and d["hist_levels_by_route"] == "scatter:18"
    # two searches x two buckets x three lanes x two trees x 18 levels
    assert toy["levels"] == {"pallas": 0, "matmul": 0, "scatter": 2 * 2 * 3 * 2 * 18}


def test_the_new_readers_read_the_spans_and_nothing_without_them(toy):
    run = toy["run"]
    ctx = {"traced_search": toy["searches"][1], "searches": toy["searches"], "trace": None,
           "cell": toy["cell"], "peaks": None}
    assert run.load_module("layer_metrics/forest_buckets.py").read(ctx) == 2
    assert run.load_module("layer_metrics/forest_dispatches.py").read(ctx) in (8, 10)
    for name in ("hist_level_roofline", "hist_device_share_pct", "tree_split_device_ms"):
        ctx["trace_reduce"] = run.load_module("lib/trace_reduce.py")
        assert run.load_module(f"layer_metrics/{name}.py").read(ctx) is None
    nothing = {**ctx, "traced_search": {"job_id": "no-such-job"}}
    assert run.load_module("layer_metrics/forest_buckets.py").read(nothing) is None
    assert run.load_module("layer_metrics/forest_dispatches.py").read(nothing) is None


def test_the_cells_four_trials_are_four_chunked_buckets(monkeypatch):
    """Every hyperparameter of the cell's space is static, so each trial is
    a bucket of its own, and at the cell's shape each bucket's fit is four
    chunks of one tree. Host-side facts only: nothing is staged or built."""
    for name in ("_staged_device", "_staged_mesh", "_build_executable", "_dispatch_span"):
        monkeypatch.setattr(trial_map, name, lambda *a, **k: pytest.fail("device path touched"))
    run = _load_run()
    cell = run.load_cell(CELL)
    ds = cell["config"]["dataset"]
    n, d, c = ds["n_samples"], ds["n_features"], ds["n_classes"]
    kernel = get_kernel("RandomForestClassifier")
    fixed = cell["config"]["estimator"]["params"]
    combos = run.search_kind(cell).expected(cell["traffic"], 7)
    assert len({json.dumps(p, sort_keys=True) for p in combos}) == 4
    prepared = {"xb": np.broadcast_to(np.int32(0), (n, d)),
                "xb_cont": np.broadcast_to(np.int32(0), (n, 10)),
                "xb_coarse": np.broadcast_to(np.int32(0), (n, 44))}
    keys = set()
    for p in combos:
        static_key, hyper = kernel.canonicalize({**fixed, **p})
        keys.add(static_key)
        assert hyper == {}
        static = trial_map._resolved_static(kernel, static_key, n, d, c)
        assert (static["_W"], static["_levels"], static["_n_bins"]) == (1536, 24, 48)
        assert static["_wsched"] == (1536, 17, 512) and static["_nb_sched"] == (256, 16)
        plan = trial_map.plan_bucket(kernel, static, [hyper], prepared, n=n, d=d,
                                     n_classes=c, n_splits=6)
        assert plan.engine == "chunked" and plan.chunk == 1 and plan.split_width is None
        assert plan.chunk_plan == {"n_chunks": fixed["n_estimators"], "trees_per_chunk": 1}
    assert len(keys) == 4
