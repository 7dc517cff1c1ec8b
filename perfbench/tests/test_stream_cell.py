"""CPU tests of the streamed-engine cell ``logreg_mnist8m.rs32`` (PR 40): its
manifest entries, its real configuration and traffic files at a toy size
through the harness (the table over half a toy stage budget, so the
streamed engine runs it under ``CS230_STREAM=auto``), its four readers on
the toy run's spans and on hand-made events, and its generator.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_stream_cell.py -q

The cell's entries are found by name and by ``workloads == [CELL]``, never
by their place in the manifest: a later cell appends after them. Nothing
here is a measurement: a CPU run proves counts and control flow.
"""

import json
import os
import re

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from test_perfbench import BENCH, MANIFEST, ROOT, run  # noqa: E402 — the harness as the older tests load it

CELL, CONFIG, TRAFFIC = "logreg_mnist8m.rs32", "logreg_mnist8m", "rs32"
READERS = ("stream_roofline", "stream_wait_ms", "stream_upload_gb", "stream_pass_host_ms")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _toy_root(tmp_path, score_gap_max=0.02):
    """The cell's own files cut to what the CPU runs in seconds: 3000 rows of
    64 pixels, 4 trials, all of them compared. On the CPU the power
    iteration and the scoring keep float32 operands where the chip rounds
    them to bfloat16, and a 600-row holdout moves an accuracy in steps of
    1/600, so the score limit is the toy's, not the chip's."""
    root = tmp_path / "toy"
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir(parents=True)
    cfg = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    cfg["dataset"].update(n_samples=3000, n_features=64)
    cfg["limits"].update(score_gap_max=score_gap_max)
    json.dump(cfg, open(root / "perfbench" / "configs" / (CONFIG + ".json"), "w"))
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    traffic.update(n_iter=4, check_trials=4)
    json.dump(traffic, open(root / "perfbench" / "traffic" / (TRAFFIC + ".json"), "w"))
    json.dump(MANIFEST, open(root / "BENCHMARK.json", "w"))
    return str(root)


def test_manifest_entries_are_well_formed():
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    wl = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert cfg["source"].startswith("https://www.csie.ntu.edu.tw/") and cfg["source"].endswith("#mnist8m")
    assert all(1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
               for e in (cfg, wl))
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == ["n_samples"]
    assert wl["chips"] == 1 and wl["config"] == CONFIG and wl["traffic"] == TRAFFIC
    assert all(NAME.match(s) for s in (cfg["name"], wl["name"], wl["traffic"], *cfg["reduced"]))
    file = json.load(open(os.path.join(ROOT, cfg["file"])))
    assert file["source"] == cfg["source"] and file["reduced"] == cfg["reduced"] and file["name"] == CONFIG
    ds, est = file["dataset"], file["estimator"]
    # the source's widths: 784 pixels, 10 classes; the mlp_mnist file's pixels
    assert (ds["n_features"], ds["n_classes"]) == (784, 10)
    assert file["source_shape"]["n_samples"] == 8_100_000 and "note" in file["source_shape"]
    pixels = json.load(open(os.path.join(BENCH, "configs", "mlp_mnist.json")))["dataset"]
    assert all(ds[k] == pixels[k] for k in ("pixel_density", "contrast", "noise", "label_noise"))
    assert ds["n_samples"] < file["source_shape"]["n_samples"] and "n_samples" in file["assumed"]
    assert est["class"] == "LogisticRegression" and est["params"] == {"max_iter": 100}
    assert file["control"] == {"precision": "float8_e4m3fn"} and file["chips"] == 1
    assert set(file["why_limits"]) == set(file["limits"])
    mine = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted(READERS)
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        mod = run.load_module(f"layer_metrics/{m['name']}.py")
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (m["layer"], m["unit"], m["source"], m["moves"])
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    assert (traffic["n_iter"], traffic["cv"], traffic["check_trials"]) == (32, 5, 8)
    assert traffic["param_distributions"] == json.load(
        open(os.path.join(BENCH, "traffic", "rs128.json")))["param_distributions"]


def test_the_table_streams_under_auto_at_the_cells_size(monkeypatch):
    """5.02 GB of float32 rows against a v5e's stage budget (0.4 of its
    16.9 GB): over half of it, with no valve set."""
    from cs230_distributed_machine_learning_tpu.data import streaming

    monkeypatch.delenv("CS230_STREAM", raising=False)
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", str(0.4 * 16.9e3))
    ds = run.load_cell(CELL)["config"]["dataset"]
    nbytes = ds["n_samples"] * ds["n_features"] * 4
    assert streaming.should_stream(nbytes) and nbytes < 0.75 * 0.4 * 16.9e9


def test_the_cell_reads_correct_at_a_toy_size_and_its_span_readers_read(tmp_path, monkeypatch):
    from cs230_distributed_machine_learning_tpu.models import logistic

    monkeypatch.setattr(logistic, "_STREAM_FN_CACHE", {})  # a run's process builds its programs
    monkeypatch.delenv("CS230_STREAM", raising=False)
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "1")  # 768 KB of rows: over half of it
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "1000")
    r, values = run.run_cell(CELL, 2**31 + 40, 0.5, True, root=_toy_root(tmp_path),
                             require_tpu=False, state_dir=str(tmp_path / "state"))
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    assert "curve_gap_vs_yardstick" in r["compared"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    spans = TRACER.spans_for(TRACER.traces()[-1])
    said = [sp["attrs"] for sp in spans if sp["name"] == "executor.dispatch"]
    assert len(said) == 1 and said[0]["engine"] == "streamed"
    assert {k: said[0][k] for k in ("block_rows", "n_blocks", "split_lanes", "n_trials")} == {
        "block_rows": 1000, "n_blocks": 3, "split_lanes": 6, "n_trials": 4}
    kinds = [sp["attrs"]["kind"] for sp in spans if sp["name"] == "stream.pass"]
    assert kinds[:31] == ["power"] * 31 and kinds[-1] == "eval" and set(kinds[31:-1]) == {"step"}
    # a warm search finds every block in the cache
    assert m["stream_upload_gb"] == 0 and m["stream_wait_ms"] >= 0 and m["stream_pass_host_ms"] > 0
    assert m["warm_stage_mb"] == 0 and m["window_compiles"] == 0
    # the streamed engine builds and fetches as every engine does
    assert m["first_build_s"] > 0 and m["first_cost_trace_s"] == 0 and "post_fetch_host_ms" in m
    assert "device_wait_ms" in m and "search_mfu" not in m  # no peaks on the CPU
    # no device plane on the CPU: the roofline finds nothing
    assert "stream_roofline" not in m


def test_the_float8_control_in_the_programs_place_is_not_correct(tmp_path):
    """The stated control, the reference with float8 operands: its curve
    parts from the float32 reference's by more than the limit's multiple of
    the stated precision's own gap (the yardstick: bfloat16)."""
    cell = run.load_cell(CELL, _toy_root(tmp_path))
    compare = run.load_module("lib/compare.py")
    ref = run.load_module("references/LogisticRegression.py")
    seed = 2**31 + 40
    X, y = run.make_dataset(cell, seed)
    combos = run.search_kind(cell).expected(cell["traffic"], seed)
    splits = compare.split_masks(y, 5, 0.2, 42)
    params = [{"max_iter": 100, **c} for c in combos]
    want = ref.reference(X, y, 10, params, splits)
    at = np.arange(1, 100, 2)  # the curve's slots: stride 2, the last step of each

    def gap(**kw):
        out = ref.reference(X, y, 10, params, splits, **kw)
        return np.median(compare.curve_gap(out["gmax"][:, :, at].astype(np.float64), want["gmax"][:, :, at]))

    ratio = gap(precision="float8_e4m3fn") / gap(**cell["config"]["yardstick"])
    assert ratio > cell["config"]["limits"]["curve_gap_vs_yardstick"]


def test_generator_draws_prototype_images_in_row_chunks():
    rows = run.load_module("datasets/prototype_images_rows.py")
    one = run.load_module("datasets/prototype_images.py")
    import jax

    spec = {"d": 32, "c": 10, "pixel_density": 0.19, "contrast": 0.22, "noise": 0.5, "label_noise": 0.02}
    n = 2 * rows.ROW_CHUNK + 123  # three chunks, the last overlapping the second
    key = run.load_module("lib/datagen.py").seed_key(2**31 + 40)
    X, y = (np.asarray(a) for a in jax.jit(lambda k: rows.generate(k, n=n, **spec))(key))
    X1, y1 = (np.asarray(a) for a in jax.jit(lambda k: one.generate(k, n=n, **spec))(key))
    assert X.shape == (n, 32) and y.shape == (n,) and X.dtype == np.float32 and y.dtype == np.int32
    assert 0.0 <= X.min() and X.max() <= 1.0 and set(np.unique(y)) == set(range(10))
    # every row drawn (no row left at the zeros it started from), the same law
    assert (np.abs(X).sum(axis=1) > 0).all()
    assert X.mean() == pytest.approx(X1.mean(), rel=0.02) and X.std() == pytest.approx(X1.std(), rel=0.02)
    np.testing.assert_allclose(np.bincount(y, minlength=10) / n, np.bincount(y1, minlength=10) / n, atol=0.01)


def _ctx(cell, events, passes, rows=1000, n_blocks=3, n_trials=4):
    tr = run.load_module("lib/trace_reduce.py")
    streamed = run.load_module("lib/streamed.py")
    dispatch = {"name": "executor.dispatch", "span_id": "d", "parent_id": None, "start": 0.0, "end": 1.0,
                "attrs": {"engine": "streamed", "block_rows": rows, "n_blocks": n_blocks,
                          "split_lanes": 6, "n_trials": n_trials}}
    chunk = {"dispatch": dispatch, "waits": [],
             "passes": [{"name": "stream.pass", "attrs": {"kind": k}} for k in passes]}
    return {"trace": tr.reduce_trace({"devices": {0: events}, "labels": {}, "host": []}, 0.2, 1),
            "trace_reduce": tr, "flops": run.load_module("lib/flops.py"), "cell": cell, "chips": 1,
            "peaks": run.load_module("lib/peaks.py").peaks_for("TPU v5 lite"),
            "traced_search": {"job_id": "toy"}, "_chunks": [chunk], "_streamed": streamed}


def test_roofline_reads_the_block_programs_by_their_block_height(monkeypatch):
    cell = run.load_cell(CELL)
    roof = run.load_module("layer_metrics/stream_roofline.py")
    events = [(0.0, 2e6, "%fusion.1 = f32[4,6,10,1000]{3,2,1,0} fusion(bf16[1000,65]{1,0} %p), kind=kLoop"),
              (2e6, 3e6, "%convolution.2 = f32[4,6,65,10]{3,2,1,0} convolution(bf16[4,6,10,1000]{3,2,1,0} %a, "
                             "bf16[1000,65]{1,0} %b)"),
              (3e6, 9e6, "%fusion.9 = f32[4,6,65,10]{3,2,1,0} fusion(f32[4,6,65,10]{3,2,1,0} %w)"),
              (9e6, 10e6, "%fusion.10 = f32[10000]{0} fusion(f32[10000]{0} %x)")]
    ctx = _ctx(cell, events, ["power"] * 31 + ["step"] * 100 + ["eval"])
    monkeypatch.setattr(roof._streamed(), "chunks", lambda search: ctx["_chunks"])
    assert roof.block_pattern(1000) == r"[\[,]1000[\],]"
    got = roof.read(ctx)
    least = roof.least_seconds(ctx["_chunks"], 784, 10, ctx["peaks"], ctx["flops"])
    assert got == pytest.approx(100.0 * least / 3e-3)  # the first two ops only
    # by hand: a step pass at 3000 rows, 24 lanes, 785 inputs, 10 classes
    f, b = roof.pass_work("step", 3000, 784, 10, 6, 24)
    assert f == 4 * 3000 * 785 * 240 and b == 3000 * (4 * 784 + 24 + 4) + 8 * 24 * 3000 * 10
    assert roof.pass_work("power", 3000, 784, 10, 6, 24)[0] == 4 * 3000 * 785 * 6
    # a program without the spans, or a trace without the ops, gives nothing
    monkeypatch.setattr(roof._streamed(), "chunks", lambda search: [])
    assert roof.read(ctx) is None
    monkeypatch.setattr(roof._streamed(), "chunks", lambda search: ctx["_chunks"])
    assert roof.read(_ctx(cell, events[2:], ["step"])) is None


def test_span_readers_on_hand_made_chunks(monkeypatch):
    host = run.load_module("layer_metrics/stream_pass_host_ms.py")
    wait = run.load_module("layer_metrics/stream_wait_ms.py")
    upload = run.load_module("layer_metrics/stream_upload_gb.py")
    dispatch = {"start": 0.0, "end": 2.0, "attrs": {}}
    passes = [{"attrs": {"kind": "step", "wait_s": 0.1, "dispatch_s": 0.2, "uploaded_bytes": 5e8}},
              {"attrs": {"kind": "step", "wait_s": 0.0, "dispatch_s": 0.3, "uploaded_bytes": 0}}]
    waits = [{"start": 0.5, "end": 1.5}]
    chunk = {"dispatch": dispatch, "passes": passes, "waits": waits}
    by_search = {"a": [chunk], "b": [chunk, chunk], "c": []}
    monkeypatch.setattr(host._streamed(), "chunks", lambda search: by_search[search["job_id"]])
    ctx = {"searches": [{"job_id": k} for k in by_search]}
    assert host.host_seconds(chunk) == pytest.approx(2.0 - 0.6 - 1.0)
    # the mean over the searches that streamed: (1 + 2) chunks over two searches
    assert host.read(ctx) == pytest.approx(1e3 * 0.4 * 1.5)
    assert wait.read(ctx) == pytest.approx(1e3 * 0.1 * 1.5)
    assert upload.read(ctx) == pytest.approx(0.5 * 1.5)
    assert wait.read({"searches": [{"job_id": "c"}]}) is None
