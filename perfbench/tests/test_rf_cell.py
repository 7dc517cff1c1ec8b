"""CPU tests of the forest cell ``rf_covertype.rs4`` (PR 32): its real
configuration and traffic files at a toy size through the harness, its work
model by hand, and its five readers on hand-made events and on the toy
run's spans.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_rf_cell.py -q

Nothing here is a measurement: a CPU run proves counts and control flow.
"""

import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from test_perfbench import BENCH, ROOT, run  # noqa: E402 — the harness as the older tests load it

CELL, CONFIG, TRAFFIC = "rf_covertype.rs4", "rf_covertype", "rs4"
READERS = ("hist_level_roofline", "hist_device_share_pct", "forest_buckets",
           "forest_dispatches", "tree_split_device_ms")


def _toy_root(tmp_path):
    """The cell's own files cut to what the CPU compiles in under a minute:
    600 rows of 4 continuous and 2 + 8 one-hot columns (the CPU's scatter
    histogram unrolls a segment-sum a feature a level), 2 trees, cv=2, and
    the two ``max_features`` settings as the search's two buckets."""
    root = tmp_path / "toy"
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir(parents=True)
    cfg = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    cfg["dataset"].update(n_samples=600, n_features=14, n_continuous=4, onehot_blocks=[2, 8])
    cfg["estimator"]["params"]["n_estimators"] = 2
    cfg["limits"].update(score_gap_max=0.009, score_gap_mean=0.003)  # a held-out row of 120
    json.dump(cfg, open(root / "perfbench" / "configs" / (CONFIG + ".json"), "w"))
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    traffic.update(n_iter=2, cv=2, check_trials=2,
                   param_distributions={"max_features": ["sqrt", "log2"], "min_samples_leaf": [1]})
    json.dump(traffic, open(root / "perfbench" / "traffic" / (TRAFFIC + ".json"), "w"))
    json.dump(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))), open(root / "BENCHMARK.json", "w"))
    return str(root)


def test_the_cell_reads_correct_at_a_toy_size_and_its_span_readers_read(tmp_path, monkeypatch):
    monkeypatch.setenv("CS230_TREE_DEEP_N", "200")  # the deep arena, as at the cell's rows
    monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "1e7")  # the chunked engine: two chunks of one tree
    r, values = run.run_cell(CELL, 2**31 + 32, 0.5, True, root=_toy_root(tmp_path),
                             require_tpu=False, state_dir=str(tmp_path / "state"))
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    assert r["attempted"] == 2 * (1 + values["searches"]) and values["searches"] >= 1
    m = {k: v["value"] for k, v in r["metrics"].items()}
    # two buckets; each init + two steps + eval and the curve's sampled eval
    assert m["forest_buckets"] == 2 and m["forest_dispatches"] == 2 * 5
    assert m["window_compiles"] == 0 and m["warm_stage_mb"] == 0
    assert m["search_untraced_pct"] < 5  # a 0.4 s search: the fixed 10 ms are 2%
    # no device plane on the CPU: the three trace readers find nothing
    assert not {"hist_level_roofline", "hist_device_share_pct", "tree_split_device_ms"} & set(m)


def test_work_of_the_cell_by_hand():
    flops = run.load_module("lib/flops.py")
    cell = run.load_cell(CELL)
    w = run.load_module("work/RandomForestClassifier.py")
    trees = cell["config"]["estimator"]["params"]["n_estimators"]
    assert [w.features_considered(s, 54) for s in ("sqrt", "log2", None, 0.5, 9)] == [7, 5, 7, 27, 9]
    widths = w.frontier_widths(cell["config"]["arena"])
    assert len(widths) == 24 and widths[:12] == [2 ** i for i in range(11)] + [1536]
    assert widths[16] == 1536 and widths[17:] == [512] * 7
    work = w.search_work(cell, flops)
    n = cell["config"]["dataset"]["n_samples"]
    assert n == 232_405  # two fifths of Covertype's 581 012 rows
    train, held = flops.split_rows(n, 5, 0.2)
    assert train == 185_924 + 5 * (n - n / 5) and held == 46_481 + n
    # 4 trials x trees x training rows x 6 features on average x 7 classes x 24 levels
    assert work["fit_flops"] == pytest.approx(4 * trees * train * 6 * 7 * 24)
    assert work["kernel_flops"] == work["fit_flops"]
    assert work["score_flops"] == pytest.approx(4 * trees * held * (24 + 7))
    rows = 24 * train * (6 + 2)
    cells = 6 * sum(wd * 6 * (48 if 2 * wd < 256 else 16) * 7 for wd in widths) * 4
    assert work["kernel_bytes"] == pytest.approx(4 * trees * (rows + cells))
    least, bound = flops.roofline(work["kernel_flops"], work["kernel_bytes"],
                                  run.load_module("lib/peaks.py").peaks_for("TPU v5 lite"))
    assert bound == "memory" and least < 0.1  # scatter adds are cheap: the chip is not


def test_trace_readers_on_hand_made_events():
    tr = run.load_module("lib/trace_reduce.py")
    flops = run.load_module("lib/flops.py")
    ms = 1e6  # events are in nanoseconds
    hist = "%level_histogram.12 = f32[6,24,448,512]{3,2,1,0} custom-call(s32[6,232448,1] %a), custom_call_target=\"tpu_custom_call\""
    topk = "%sort.3 = (f32[6,3072], s32[6,3072]) sort(%x, %iota)"
    route = "%fusion.77 = pred[6,232405,1536] fusion(%node, %frontier), kind=kLoop"
    # the XLA form (what `auto` runs on a TPU since PR 32) has no name of its
    # own: its row loop is known by its accumulators, as the v5e compiler
    # writes them; the level scan around it carries none and is not counted
    xla = ("%while.241 = (s32[]{:T(128)}, s32[6,10752,160]{1,2,0:T(8,128)}, s32[6,10752,176]{1,2,0:T(8,128)}, "
           "f32[6,245760,7]{1,2,0:T(8,128)}) while((s32[]{:T(128)}, s32[6,10752,160]{1,2,0:T(8,128)}) %tuple.9), "
           "condition=%wide.region_1, body=%wide.region_2")
    scan = ("%while.246 = (s32[]{:T(128)}, f32[6,1536,10,16,8]{4,3,2,1,0}, f32[6,1536,44,4,8]{4,3,2,1,0}, "
            "s32[6,6,1536]{2,1,0}) while((s32[]{:T(128)}) %tuple.3), condition=%c, body=%b")
    events = [(0, 60 * ms, hist), (60 * ms, 70 * ms, topk), (70 * ms, 100 * ms, route),
              (120 * ms, 180 * ms, scan), (130 * ms, 170 * ms, xla), (180 * ms, 200 * ms, hist)]
    cell = run.load_cell(CELL)
    ctx = {"trace": tr.reduce_trace({"devices": {0: events}, "labels": {}, "host": []}, 0.2, 1),
           "trace_reduce": tr, "flops": flops, "cell": cell, "chips": 1,
           "peaks": run.load_module("lib/peaks.py").peaks_for("TPU v5 lite"),
           "work": run.load_module("work/RandomForestClassifier.py").search_work(cell, flops)}
    reader = lambda name: run.load_module(f"layer_metrics/{name}.py").read(ctx)
    # 120 ms of histogram ops (either form) in 180 ms of busy time
    assert reader("hist_device_share_pct") == pytest.approx(100 * 120 / 180)
    least, _ = flops.roofline(ctx["work"]["kernel_flops"], ctx["work"]["kernel_bytes"], ctx["peaks"])
    assert reader("hist_level_roofline") == pytest.approx(100 * least / 0.120)
    fits = 4 * 6 * cell["config"]["estimator"]["params"]["n_estimators"]
    assert reader("tree_split_device_ms") == pytest.approx(180.0 / fits)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_nothing_returns_nothing(name):
    """The parent commit is run with these readers: no forest span, no
    histogram op, no device trace must read as nothing, never raise."""
    tr = run.load_module("lib/trace_reduce.py")
    logreg = "%packed_nesterov_step.7 = (f32[1,64,5376]) custom-call(bf16[12288,64] %a)"
    flops = run.load_module("lib/flops.py")
    cell = run.load_cell("logreg_rows5m.rs128")
    base = {"trace_reduce": tr, "flops": flops, "cell": cell, "chips": 1, "peaks": None,
            "traced_search": {"job_id": "no-such-job"}, "searches": [],
            "work": run.load_module("work/LogisticRegression.py").search_work(cell, flops)}
    read = run.load_module(f"layer_metrics/{name}.py").read
    assert read({**base, "trace": None}) is None
    other = tr.reduce_trace({"devices": {0: [(0, 5e6, logreg)]}, "labels": {}, "host": []}, 0.1, 1)
    if name != "tree_split_device_ms":  # busy time over fits reads wherever a device was busy
        assert read({**base, "trace": other}) is None


def test_generator_is_a_function_of_the_seed_and_keeps_the_column_kinds():
    import numpy as np

    spec = dict(json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))["dataset"])
    spec["n_samples"] = 4000
    make = lambda seed: run.make_dataset({"config": {"dataset": spec}}, seed)
    big = 2**31 + 12345
    (X1, y1), (X2, y2), (X3, _), (X4, _) = make(big), make(big), make(big + 1), make(12345)
    assert X1.dtype == np.float32 and y1.dtype == np.int32 and X1.shape == (4000, 54)
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)
    assert not np.array_equal(X1, X3) and not np.array_equal(X1, X4)
    # ten continuous columns, then a 4-way and a 40-way one-hot block
    assert all(len(np.unique(X1[:, f])) > 1000 for f in range(10))
    assert set(np.unique(X1[:, 10:])) == {0.0, 1.0}
    assert (X1[:, 10:14].sum(1) == 1).all() and (X1[:, 14:].sum(1) == 1).all()
    # some soil types are common and some rare, as in the source
    share = X1[:, 14:].mean(0)
    assert share.max() > 0.1 and share.min() < 0.01
    assert len(np.unique(y1)) == 7
    # the binning the program documents gives the one-hot columns a coarse group
    family = run.load_module("references/RandomForestClassifier.py")
    codes, fine = family.bin_codes(X1, 48)
    assert fine[:10].all() and not fine[10:].any() and codes[:, :10].max() == 47
