"""CPU tests of the exact-dual SVC cell ``svc_mnist30k.rs4svc`` (PR 42): its
manifest entries, its work file by hand at the cell's shapes, its two
readers on hand-made spans and counters and on a program without them, its
real configuration and traffic files at a toy size through the harness, and
its controls in the program's place.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_svc_cell.py -q

The cell's entries are found by name and by ``workloads == [CELL]``, never
by their place in the manifest: a later cell appends after them. Nothing
here is a measurement: a CPU run proves counts and control flow.
"""

import json
import os
import re

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from test_perfbench import BENCH, MANIFEST, ROOT, run  # noqa: E402 — the harness as the older tests load it

CELL, CONFIG, TRAFFIC = "svc_mnist30k.rs4svc", "svc_mnist30k", "rs4svc"
READERS = ("svc_fit_roofline", "svc_ascent_steps")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SEED = 2**31 + 42


#: the toy's curve limit: on the CPU the program's Gram cross term and
#: intercepts are float32 where the bfloat16 yardstick rounds them, so the
#: yardstick is not the program's precision here and the program read 3.0
#: of it at 600 rows (on the chip, where both take one bfloat16 pass, 1.003);
#: the float8 control reads 54 and the fault 47 655 at this toy
TOY_CURVE_LIMIT = 6.0


def _toy_root(tmp_path, rows=600, n_iter=3):
    """The cell's own files cut to what the CPU runs in seconds: 600 rows of
    784 pixels, 3 trials, all of them compared, the curve held to the toy's
    limit."""
    root = tmp_path / "toy"
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir(parents=True)
    cfg = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    cfg["dataset"].update(n_samples=rows)
    cfg["limits"].update(curve_gap_vs_yardstick=TOY_CURVE_LIMIT)
    json.dump(cfg, open(root / "perfbench" / "configs" / (CONFIG + ".json"), "w"))
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    traffic.update(n_iter=n_iter, check_trials=n_iter)
    json.dump(traffic, open(root / "perfbench" / "traffic" / (TRAFFIC + ".json"), "w"))
    json.dump(MANIFEST, open(root / "BENCHMARK.json", "w"))
    return str(root)


def test_manifest_entries_are_well_formed():
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    wl = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert cfg["source"].startswith("https://www.csie.ntu.edu.tw/") and cfg["source"].endswith("#mnist")
    assert all(1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
               for e in (cfg, wl))
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == ["n_samples"]
    assert wl["chips"] == 1 and wl["config"] == CONFIG and wl["traffic"] == TRAFFIC
    assert all(NAME.match(s) for s in (cfg["name"], wl["name"], wl["traffic"], *cfg["reduced"]))
    file = json.load(open(os.path.join(ROOT, cfg["file"])))
    assert file["source"] == cfg["source"] and file["reduced"] == cfg["reduced"] and file["name"] == CONFIG
    ds, est = file["dataset"], file["estimator"]
    # the source's widths: 784 pixels, 10 classes; the mlp_mnist file's pixels
    assert (ds["n_features"], ds["n_classes"]) == (784, 10) and ds["kind"] == "prototype_images"
    assert file["source_shape"]["n_samples"] == 60_000 and "note" in file["source_shape"]
    pixels = json.load(open(os.path.join(BENCH, "configs", "mlp_mnist.json")))["dataset"]
    assert all(ds[k] == pixels[k] for k in ("pixel_density", "contrast", "noise", "label_noise"))
    assert ds["n_samples"] == 30_000 and "n_samples" in file["assumed"]
    assert est == {"class": "SVC", "module": "sklearn.svm",
                   "params": {"kernel": "rbf", "gamma": "scale"}}
    assert file["control"] == {"precision": "float8_e4m3fn"} and file["chips"] == 1
    assert file["yardstick"] == {"precision": "bfloat16"}
    assert set(file["why_limits"]) == set(file["limits"])
    assert {"score_gap_max", "curve_gap_vs_yardstick"} <= set(file["limits"])
    mine = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted(READERS)
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        mod = run.load_module(f"layer_metrics/{m['name']}.py")
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (m["layer"], m["unit"], m["source"], m["moves"])
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    assert (traffic["n_iter"], traffic["cv"], traffic["check_trials"]) == (4, 5, 2)
    assert traffic["param_distributions"] == {"C": {"dist": "loguniform", "args": [0.1, 100.0]}}


def test_the_cells_plan_is_a_split_a_dispatch():
    """At the cell's 30 000 x 784 on a v5e (16.9 GB), the lane estimate
    (7.3 GB) puts one trial in a dispatch and one split in a dispatch."""
    import numpy as np

    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    kernel = get_kernel("SVC")
    static = trial_map._resolved_static(kernel, kernel.canonicalize(
        {"kernel": "rbf", "gamma": "scale", "C": 1.0})[0], 30_000, 784, 10)
    assert not static.get("_nystrom")
    X = np.broadcast_to(np.float32(0), (30_000, 784))
    old = trial_map._backend.device_memory_mb
    trial_map._backend.device_memory_mb = lambda: 16.9e3
    try:
        bp = trial_map.plan_bucket(kernel, static, [{"C": 1.0}] * 4, X, n=30_000, d=784,
                                   n_classes=10, n_splits=6)
    finally:
        trial_map._backend.device_memory_mb = old
    assert (bp.engine, bp.chunk, bp.split_width) == ("generic", 1, 1)


def test_work_of_the_cell_by_hand():
    cell = run.load_cell(CELL)
    work = run.load_module("work/SVC.py")
    w = work.search_work(cell, run.load_module("lib/flops.py"))
    # six splits of 24 000 training and 6 000 held-out rows (the holdout's
    # test share rounded up is 6 000; a fold holds out a fifth)
    assert work.split_rows(30_000, 5, 0.2) == [(24_000.0, 6_000.0)] * 6
    assert w["fit_flops"] == pytest.approx(4 * 6 * 2 * 24_000**2 * 784)  # 4 trials' Gram builds
    assert w["score_flops"] == pytest.approx(4 * 6 * 2 * 6_000 * 24_000 * 784)
    assert w["lane_step_bytes"] == pytest.approx(2 * 24_000**2)  # 1.152 GB of bfloat16 a step
    roof = run.load_module("layer_metrics/svc_fit_roofline.py")
    peaks = run.load_module("lib/peaks.py").peaks_for("TPU v5 lite")
    least = roof.least_seconds(w, 600 * 24, peaks, run.load_module("lib/flops.py"))
    # 24 lanes at the cap: 14 400 streams of 1.152 GB at 819 GB/s, and the
    # builds and scoring at 197 TFLOP/s
    assert least == pytest.approx(14_400 * 1.152e9 / 819e9 + (w["fit_flops"] + w["score_flops"]) / 197e12)


def _spans(monkeypatch, roof, spans):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    monkeypatch.setattr(TRACER, "trace_for_job", lambda job_id: "t" if spans else None)
    monkeypatch.setattr(TRACER, "spans_for", lambda tid: spans)


def test_readers_read_the_spans_and_the_counter_and_nothing_without_them(monkeypatch):
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    cell = run.load_cell(CELL)
    flops = run.load_module("lib/flops.py")
    roof = run.load_module("layer_metrics/svc_fit_roofline.py")
    steps = run.load_module("layer_metrics/svc_ascent_steps.py")
    peaks = run.load_module("lib/peaks.py").peaks_for("TPU v5 lite")
    ctx = {"cell": cell, "peaks": peaks, "flops": flops, "work": run.load_module("work/SVC.py").search_work(cell, flops),
           "trace": {"fullest": 0, "busy_s_fullest": 40.0}, "traced_search": {"job_id": "j"},
           "searches": [{"job_id": "j"}]}
    dispatch = {"name": "executor.dispatch", "attrs": {"engine": "generic", "svm.solver": "exact",
                                                       "svm.steps_sum": 3000}}
    _spans(monkeypatch, roof, [dispatch, dict(dispatch), {"name": "executor.fetch", "attrs": {}}])
    assert roof.ascent_steps(ctx["traced_search"]) == 6000
    assert roof.read(ctx) == pytest.approx(100.0 * roof.least_seconds(ctx["work"], 6000, peaks, flops) / 40.0)
    # a program without the span attributes, or a trace without a device
    _spans(monkeypatch, roof, [{"name": "executor.dispatch", "attrs": {"engine": "generic"}}])
    assert roof.read(ctx) is None
    _spans(monkeypatch, roof, [dispatch])
    assert roof.read({**ctx, "trace": None}) is None and roof.read({**ctx, "peaks": None}) is None
    # the counter over the run's lanes: 4 trials x 6 splits x 2 searches
    counter = REGISTRY.counter("tpuml_svm_ascent_steps_total")
    monkeypatch.setattr(counter, "value", lambda **labels: 9600.0 if labels == {"solver": "exact"} else 0.0)
    assert steps.read(ctx) == pytest.approx(9600 / 48)
    monkeypatch.setattr(counter, "value", lambda **labels: 0.0)
    assert steps.read(ctx) is None


def test_the_cell_reads_correct_at_a_toy_size_and_its_readers_read(tmp_path):
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY, TRACER

    before = REGISTRY.counter("tpuml_svm_ascent_steps_total").value(solver="exact")
    r, values = run.run_cell(CELL, SEED, 0.5, True, root=_toy_root(tmp_path),
                             require_tpu=False, state_dir=str(tmp_path / "state"))
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    assert "curve_gap_vs_yardstick" in r["compared"]
    spans = TRACER.spans_for(TRACER.traces()[-1])
    said = [sp["attrs"] for sp in spans if sp["name"] == "executor.dispatch"]
    assert len(said) == 1 and said[0]["engine"] == "generic" and said[0]["svm.solver"] == "exact"
    assert said[0]["svm.machines"] == 45 and said[0]["svm.gram_bytes"] == 600 * 600 * 2
    assert 0 < said[0]["svm.steps_max"] <= 600 and said[0]["svm.steps_sum"] <= 3 * 6 * said[0]["svm.steps_max"]
    # the dispatch span holds the chunk's wait and its fetch
    kids = {sp["name"] for sp in spans if sp["parent_id"] == next(
        s["span_id"] for s in spans if s["name"] == "executor.dispatch")}
    assert {"executor.wait", "executor.fetch"} <= kids
    m = {k: v["value"] for k, v in r["metrics"].items()}
    ran = REGISTRY.counter("tpuml_svm_ascent_steps_total").value(solver="exact") - before
    assert m["svc_ascent_steps"] * 3 * 6 * (1 + values["searches"]) == pytest.approx(ran)
    assert "svc_fit_roofline" not in m and "search_mfu" not in m  # no device plane, no peaks on the CPU


def test_the_float8_control_and_the_fault_in_the_programs_place_are_not_correct(tmp_path):
    """The stated control (the reference with float8 operands) and the
    reference's fault, each put in the program's place and read by the
    comparison of a run, come out not correct; the reference at the stated
    precision (bfloat16) and the program itself come out correct."""
    import jax

    probe = run.load_module("tools/probe_limits.py")
    cell = run.load_cell(CELL, _toy_root(tmp_path))
    row = probe.readings(cell, SEED, True, jax.devices()[:1])
    assert row["program"]["correct"] is True, row["program"]
    assert row["bfloat16"]["correct"] is True, row["bfloat16"]
    family = run.load_module("references/SVC.py")
    for name in ("control", *family.FAULTS):
        assert row[name]["correct"] is False, (name, row[name])
