"""CPU tests of the boosted-trees cell ``gbt_higgs.rs8`` (PR 34): its manifest
entries, its real configuration and traffic files at a toy size through the
harness, its work model by hand, its five readers on hand-made events and
on the toy run's spans, and its generator.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_gbt_cell.py -q

Nothing here is a measurement: a CPU run proves counts and control flow.
"""

import json
import os
import re

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from test_perfbench import BENCH, MANIFEST, ROOT, run  # noqa: E402 — the harness as the older tests load it

CELL, CONFIG, TRAFFIC = "gbt_higgs.rs8", "gbt_higgs", "rs8"
READERS = ("gbt_hist_roofline", "gbt_hist_device_share_pct", "gbt_stage_device_ms",
           "gbt_lanes_per_dispatch", "gbt_dispatches")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _toy_root(tmp_path):
    """The cell's own files cut to what the CPU compiles in seconds: 3000
    rows of 7 low-level and 3 high-level columns, 4 stages of depth 4, 2
    trials, cv=2. The CPU's dot keeps float32 operands where the reference
    rounds them to bfloat16 as the configuration states, so a near-tie can
    fall the other way and every later stage then differs: on 600-1500
    held-out rows that moves an accuracy by up to a few hundredths, and the
    two score limits are widened to that (the chip's are the file's)."""
    root = tmp_path / "toy"
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir(parents=True)
    cfg = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    cfg["dataset"].update(n_samples=3000, n_features=10, n_low=7)
    cfg["estimator"]["params"].update(n_estimators=4, max_depth=4)
    cfg["limits"].update(score_gap_max=0.06, score_gap_mean=0.03)
    json.dump(cfg, open(root / "perfbench" / "configs" / (CONFIG + ".json"), "w"))
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    traffic.update(n_iter=2, cv=2, check_trials=2)
    json.dump(traffic, open(root / "perfbench" / "traffic" / (TRAFFIC + ".json"), "w"))
    json.dump(MANIFEST, open(root / "BENCHMARK.json", "w"))
    return str(root)


def test_manifest_entries_are_well_formed():
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    wl = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert MANIFEST["configs"][-1] is cfg and MANIFEST["workloads"][-1] is wl  # appended, not inserted
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cfg["source"]) <= 200 and "XGBoost" in cfg["source"] and "HIGGS" in cfg["source"]
    assert all(1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"] for e in (cfg, wl))
    assert cfg["reduced"] == ["n_samples", "n_estimators"]
    assert wl["chips"] == 1 and wl["config"] == CONFIG and wl["traffic"] == TRAFFIC
    assert all(NAME.match(s) for s in (cfg["name"], wl["name"], wl["traffic"], *cfg["reduced"]))
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1 and len(MANIFEST["workloads"]) == 5
    file = json.load(open(os.path.join(ROOT, cfg["file"])))
    assert file["source"] == cfg["source"] and file["reduced"] == cfg["reduced"] and file["name"] == CONFIG
    ds, est = file["dataset"], file["estimator"]
    # the source's shapes: 28 features, binary, depth 8, shrinkage 0.1, 128 bins
    assert (ds["n_features"], ds["n_classes"], ds["n_low"]) == (28, 2, 21)
    assert est["params"]["max_depth"] == 8 and est["params"]["learning_rate"] == 0.1
    assert file["histograms"]["n_bins"] == 128 and file["chips"] == 1 and file["mesh"] is None
    assert ds["n_samples"] <= file["source_shape"]["n_samples"] == 11_000_000
    assert est["params"]["n_estimators"] in (8, 12, 16, 24, 32)
    assert {"n_samples", "n_estimators", "n_bins"} <= set(file["assumed"])
    mine = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(READERS) == [m["name"] for m in MANIFEST["per_layer"][-5:]]
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        mod = run.load_module(f"layer_metrics/{m['name']}.py")
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (m["layer"], m["unit"], m["source"], m["moves"])
    assert next(m for m in mine if m["name"] == "gbt_hist_roofline")["unit"] == "%"
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    assert traffic["n_iter"] == 8 and traffic["cv"] == 5 and traffic["check_trials"] == 8  # every lane is compared
    assert set(traffic["param_distributions"]) == {"learning_rate", "subsample"}


def test_the_cell_reads_correct_at_a_toy_size_and_its_span_readers_read(tmp_path, monkeypatch):
    monkeypatch.setenv("CS230_TREE_CHUNK_MACS", "3e9")  # the chunked engine: two chunks of two stages
    r, values = run.run_cell(CELL, 2**31 + 34, 0.5, True, root=_toy_root(tmp_path),
                             require_tpu=False, state_dir=str(tmp_path / "state"))
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    n_searches = 1 + values["searches"]
    assert r["attempted"] == 2 * n_searches and values["searches"] >= 1
    m = {k: v["value"] for k, v in r["metrics"].items()}
    # one bucket of both traced trials, all three folds in a program: six
    # lanes; init + two steps + eval and the curve's sampled eval
    assert m["gbt_lanes_per_dispatch"] == 2 * 3 and m["gbt_dispatches"] == 5
    assert m["window_compiles"] == 0 and m["warm_stage_mb"] == 0
    # what a boosted bucket says of itself on its dispatch span (the newest trace is the last search's)
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    said = [sp["attrs"] for sp in TRACER.spans_for(TRACER.traces()[-1]) if sp["name"] == "executor.dispatch"]
    assert len(said) == 1 and said[0]["engine"] == "chunked" and said[0]["n_trials"] == 2
    assert {k: said[0][k] for k in ("stages", "hist_levels_by_route", "split_lanes", "n_chunks",
                                     "trees_per_chunk")} == {
        "stages": 4, "hist_levels_by_route": "scatter:4", "split_lanes": 3, "n_chunks": 2, "trees_per_chunk": 2}
    assert said[0]["mem_cap"] >= 2  # the trials the plan's memory budget admits a dispatch
    # no device plane on the CPU: the three trace readers find nothing
    assert not {"gbt_hist_roofline", "gbt_hist_device_share_pct", "gbt_stage_device_ms"} & set(m)
    # a learning rate moves the score: the two trials do not tie
    scores = values["numbers"]
    assert scores["score_gap_max"] <= 0.06 and scores["params_mismatch"] == 0
    # the curve's gmax (after stages 2 and 4) is compared, and lands on the reference's
    assert "curve_gap_median" in r["compared"] and scores["curve_gap_median"] < 1e-5


def test_work_of_the_cell_by_hand():
    flops = run.load_module("lib/flops.py")
    cell = run.load_cell(CELL)
    w = run.load_module("work/GradientBoostingClassifier.py")
    est = cell["config"]["estimator"]["params"]
    stages, n = est["n_estimators"], cell["config"]["dataset"]["n_samples"]
    work = w.search_work(cell, flops)
    train, held = flops.split_rows(n, 5, 0.2)
    # 8 trials x stages x training rows x 28 features x (g, h) x 8 levels
    assert work["fit_flops"] == pytest.approx(8 * stages * train * 28 * 2 * 8)
    assert work["kernel_flops"] == work["fit_flops"]
    assert work["score_flops"] == pytest.approx(8 * stages * held * 9)
    rows = 8 * train * (28 + 8)  # a byte a code, two float32 statistics, a level
    cells = 6 * 4 * sum(2 ** lv * 28 * 128 * 2 for lv in range(8))
    assert work["kernel_bytes"] == pytest.approx(8 * stages * (rows + cells))
    least, bound = flops.roofline(work["kernel_flops"], work["kernel_bytes"],
                                  run.load_module("lib/peaks.py").peaks_for("TPU v5 lite"))
    assert bound == "memory" and least < 0.5  # scatter adds are cheap: the chip is not


def _ctx(cell, events):
    tr = run.load_module("lib/trace_reduce.py")
    flops = run.load_module("lib/flops.py")
    return {"trace": tr.reduce_trace({"devices": {0: events}, "labels": {}, "host": []}, 0.2, 1),
            "trace_reduce": tr, "flops": flops, "cell": cell, "chips": 1,
            "peaks": run.load_module("lib/peaks.py").peaks_for("TPU v5 lite"),
            "traced_search": {"job_id": "no-such-job"}, "searches": [],
            "work": run.load_module("work/GradientBoostingClassifier.py").search_work(cell, flops)}


def test_trace_readers_on_hand_made_events():
    ms = 1e6  # events are in nanoseconds
    # the row loops as the v5e compiler writes them (my deviceless compile of
    # the step program at the cell's shape, PR 34): one float32 accumulator
    # [trials, lanes, 1, 2 x nodes, 28 x 128] beside the loop's operands
    loop = lambda nodes: (  # noqa: E731
        f"%while.2{nodes} = (s32[]{{:T(128)}}, f32[8,6,1,{2 * nodes},3584]{{4,3,1,2,0:T(8,128)S(1)}}, s32[62]{{0:T(128)S(1)}}, "
        "s32[8,6,1,1015808]{3,1,2,0:T(4,128)}, bf16[8,6,1,1015808,2]{3,4,1,2,0:T(2,128)(2,1)}, s32[1015808,28]{0,1:T(8,128)}) "
        "while(%tuple.641), condition=%wide.region_72.161.clone, body=%wide.region_71.160.clone.sunk")
    # the stage scan round them carries no accumulator and is not counted
    stage = ("%while.216 = (s32[]{:T(128)}, f32[8,6,1000000,2]{2,3,1,0:T(2,128)S(1)}, s32[1000000,28]{0,1:T(8,128)}, "
             "bf16[1000000,28]{0,1:T(8,128)(2,1)}) while(%tuple.600), condition=%c, body=%b")
    route = "%fusion.77 = pred[8,6,1000000]{2,1,0} fusion(%node, %bf, %bb), kind=kLoop"
    got = "%while.218 = f32[8,6,1,2,3584]{4,1,3,2,0:T(4,128)S(1)} get-tuple-element(%while.217), index=1"
    events = [(0, 200 * ms, stage), (10 * ms, 40 * ms, loop(1)), (40 * ms, 41 * ms, got), (50 * ms, 70 * ms, route),
              (80 * ms, 150 * ms, loop(64)), (150 * ms, 160 * ms, route)]
    cell = run.load_cell(CELL)
    ctx = _ctx(cell, events)
    reader = lambda name: run.load_module(f"layer_metrics/{name}.py").read(ctx)  # noqa: E731
    # 100 ms of histogram loops in 200 ms of busy time
    assert reader("gbt_hist_device_share_pct") == pytest.approx(100 * 100 / 200)
    least, _ = ctx["flops"].roofline(ctx["work"]["kernel_flops"], ctx["work"]["kernel_bytes"], ctx["peaks"])
    assert reader("gbt_hist_roofline") == pytest.approx(100 * least / 0.100)
    # no span says `stages` here: the configuration's trials x splits x stages
    fits = 8 * 6 * cell["config"]["estimator"]["params"]["n_estimators"]
    assert reader("gbt_stage_device_ms") == pytest.approx(200.0 / fits)
    # the forest's pattern (int32 accumulators) is silent on these loops, and this one on the forest's
    forest = run.load_cell("rf_covertype.rs4")
    theirs = run.load_module("work/RandomForestClassifier.py").hist_op_pattern(forest)
    assert not any(re.search(theirs, e[2]) for e in events)
    xla = ("%while.241 = (s32[]{:T(128)}, s32[6,10752,160]{1,2,0:T(8,128)}, s32[6,10752,176]{1,2,0:T(8,128)}, "
           "f32[6,245760,7]{1,2,0:T(8,128)}) while((s32[]{:T(128)}) %tuple.9), condition=%c, body=%b")
    assert not re.search(ctx["work"]["hist_op_pattern"], xla)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_nothing_returns_nothing(name):
    """The parent commit is run with these readers, and so is every other
    cell's work file: no boosted span, no histogram op, no device trace, no
    pattern must read as nothing, never raise."""
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
    if name != "gbt_stage_device_ms":  # busy time over stages reads wherever a device was busy
        assert read({**base, "trace": other}) is None
    mine = _ctx(run.load_cell(CELL), [(0, 5e6, logreg)])
    if name in ("gbt_hist_roofline", "gbt_hist_device_share_pct"):
        assert read(mine) is None  # a pattern, and no op that matches it


def test_generator_is_a_function_of_the_seed_and_keeps_the_column_kinds():
    import numpy as np

    spec = dict(json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))["dataset"])
    spec["n_samples"] = 20000
    make = lambda seed: run.make_dataset({"config": {"dataset": spec}}, seed)  # noqa: E731
    big = 2**31 + 12345
    (X1, y1), (X2, y2), (X3, _), (X4, _) = make(big), make(big), make(big + 1), make(12345)
    assert X1.dtype == np.float32 and y1.dtype == np.int32 and X1.shape == (20000, 28)
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)
    assert not np.array_equal(X1, X3) and not np.array_equal(X1, X4)
    assert np.isfinite(X1).all() and set(np.unique(y1)) == {0, 1}
    assert 0.51 < y1.mean() < 0.55  # HIGGS: about 53% signal
    # 28 continuous columns: every one takes the 128 bins the program cuts
    family = run.load_module("references/GradientBoostingClassifier.py")
    codes = family.bin_codes(X1, 128)
    assert codes.shape == (20000, 28) and (codes.max(0) == 127).all()
    # some low-level columns have heavy tails; the root sums of squares among
    # the high-level columns sit well above zero, the products round it
    Z = (X1 - X1.mean(0)) / X1.std(0)
    kurt = (Z ** 4).mean(0)
    assert (kurt[:7] > 4).all() and (kurt[7:21] < 4).all()
    above = np.median(X1[:, 21:], 0) / X1[:, 21:].std(0)
    assert (above > 1.5).sum() == 3 and (np.abs(above) < 0.5).sum() == 4
