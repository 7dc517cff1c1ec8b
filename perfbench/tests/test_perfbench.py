"""CPU tests of the benchmark's own arithmetic, reduction and harness.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

Nothing here is a measurement: a CPU run proves counts and control flow.
"""

import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _load_run():
    if "perfbench_run" in sys.modules:
        return sys.modules["perfbench_run"]
    spec = importlib.util.spec_from_file_location("perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["perfbench_run"] = mod
    spec.loader.exec_module(mod)
    return mod


run = _load_run()
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------ the manifest

def test_manifest_names_units_and_files():
    cells = {w["name"] for w in MANIFEST["workloads"]}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for entry in MANIFEST["workloads"] + MANIFEST["configs"] + MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        traffic = json.load(open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(BENCH, "searches", traffic["search"] + ".py"))
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for c in MANIFEST["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for per_name in (("references", cfg["estimator"]["class"]), ("work", cfg["estimator"]["class"]),
                         ("datasets", cfg["dataset"]["kind"])):
            assert os.path.exists(os.path.join(BENCH, per_name[0], per_name[1] + ".py")), per_name
        assert set(cfg["limits"]) >= {"failed_trials", "params_mismatch", "mean_gap", "best_gap"}
        assert any(k.startswith("score_gap_") for k in cfg["limits"])
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        mod = run.load_module(f"layer_metrics/{m['name']}.py")
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (m["layer"], m["unit"], m["source"], m["moves"])
        assert m["moves"] in e2e
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", reporting)) <= reporting
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, len(cells) // 4)


# ------------------------------------------------------- operations, bytes

def test_work_of_the_cells_by_hand():
    flops = run.load_module("lib/flops.py")
    train, held = flops.split_rows(5_000_000, 5, 0.2)
    assert train == 4_000_000 + 5 * 4_000_000 and held == 1_000_000 + 5_000_000
    w = run.load_module("work/LogisticRegression.py").search_work(run.load_cell("logreg_rows5m.rs128"), flops)
    # 100 steps x 128 trials x 2 matmuls x 2 FLOPs x 24M training rows x 55 x 7
    assert w["fit_flops"] == pytest.approx(100 * 128 * 4 * 24e6 * 55 * 7)
    assert w["score_flops"] == pytest.approx(128 * 2 * 6e6 * 55 * 7)
    assert w["kernel_bytes"] == pytest.approx(100 * (5e6 * 55 * 2 + 768 * 55 * 7 * 16))
    least, bound = flops.roofline(w["kernel_flops"], w["kernel_bytes"],
                                  run.load_module("lib/peaks.py").peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(w["kernel_flops"] / 197e12)
    m = run.load_module("work/MLPClassifier.py").search_work(run.load_cell("mlp_mnist.rs64"), flops)
    all_l = 784 * 512 + 512 * 512 + 512 * 10
    upper = 512 * 512 + 512 * 10
    rows = (48_000 + 5 * 48_000) * 1.0  # 60000 // 200 * 200 == 60000: no ragged tail
    assert m["fit_flops"] == pytest.approx(5 * 64 * rows * (4 * all_l + 2 * upper))
    assert m["score_flops"] == pytest.approx(64 * 2 * 72_000 * all_l)
    with pytest.raises(KeyError):
        run.load_module("lib/peaks.py").peaks_for("TPU v9")


# ----------------------------------------------------------- the generator

@pytest.mark.parametrize("kind", ["logreg_rows5m", "mlp_mnist"])
def test_generator_is_a_function_of_the_seed(kind):
    spec = dict(json.load(open(os.path.join(BENCH, "configs", kind + ".json")))["dataset"])
    spec["n_samples"] = 2000
    make = lambda seed: run.make_dataset({"config": {"dataset": spec}}, seed)
    big = 2**31 + 12345
    X1, y1 = make(big)
    X2, y2 = make(big)
    X3, y3 = make(big + 1)
    X4, _ = make(12345)  # the low 31 bits alone
    assert X1.dtype == np.float32 and y1.dtype == np.int32 and X1.shape == (2000, spec["n_features"])
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)
    assert not np.array_equal(X1, X3) and not np.array_equal(X1, X4)
    assert len(np.unique(y1)) == spec["n_classes"] or kind == "logreg_rows5m"
    assert np.isfinite(X1).all()


# ---------------------------------------------------- the trace reduction

def test_reduction_on_hand_made_events():
    tr = run.load_module("lib/trace_reduce.py")
    ev = [(0, 100, "while.1"), (10, 40, "kernel_a"), (50, 90, "kernel_a"), (60, 70, "inner"),
          (200, 260, "fusion.2"), (400, 420, "kernel_a")]
    assert tr.union_seconds(ev) == pytest.approx(180e-9)
    st = tr.self_times(ev)
    assert st["while.1"] == pytest.approx(30e-9) and st["kernel_a"] == pytest.approx(80e-9)
    assert st["inner"] == pytest.approx(10e-9) and st["fusion.2"] == pytest.approx(60e-9)
    assert tr.matching_seconds(ev, "kernel_a") == pytest.approx(90e-9)
    assert tr.matching_seconds(ev, "while|kernel") == pytest.approx(120e-9)
    assert tr.matching_seconds(ev, "epoch", {"fusion.2": "jit(f)/pallas_call[name=epoch]"}) == pytest.approx(60e-9)
    host = [(0, 500, "perfbench.search"), (255, 405, "fetch"), (90, 210, "plan")]
    gaps = tr.idle_gaps(ev, host, top=3)
    assert gaps[0] == ("fetch", pytest.approx(140e-9)) and gaps[1] == ("plan", pytest.approx(100e-9))
    assert gaps[2] == ("perfbench.search (after the last device op)", pytest.approx(80e-9))
    assert tr.short_name('%k.7 = (f32[1]) custom-call(), custom_call_target="tpu_custom_call"') == "%k.7 [tpu_custom_call]"
    red = tr.reduce_trace({"devices": {0: ev, 1: []}, "labels": {}, "host": host}, 1e-6, 1)
    assert red["busy_s"] == pytest.approx(180e-9) and red["fullest"] == 0
    assert red["device_ops"][0][0] == "kernel_a"


def test_reduction_on_the_recorded_trace():
    """A small trace recorded on the v5e (a jitted matmul loop under a
    ``perfbench.search`` annotation), kept beside this file."""
    tr = run.load_module("lib/trace_reduce.py")
    path = os.path.join(HERE, "data", "v5e_small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace")
    trace = tr.load(path)
    assert 0 in trace["devices"] and len(trace["devices"][0]) > 0
    assert any(name == "perfbench.search" for _, _, name in trace["host"])
    red = tr.reduce_trace(trace, 1.0, 1)
    assert 0 < red["busy_s"] < 1.0 and red["device_ops"]
    span = next((s, e) for s, e, name in trace["host"] if name == "perfbench.search")
    first, last = trace["devices"][0][0][0], max(e for _, e, _ in trace["devices"][0])
    slack = 5e6  # host and device clocks agree to about a millisecond
    assert span[0] - slack <= first and last <= span[1] + slack
    assert any("fusion" in name for name, _ in red["device_ops"])


# ------------------------------------------------------------- the harness

TOY_LIMITS = {  # the cells' own numbers at the toy size, limits read at that size
    "logreg_rows5m": {"score_gap_max": 0.004, "curve_gap_vs_yardstick": 3.0},
    "mlp_mnist": {"score_gap_mean": 0.1, "score_gap_sensitive": 0.012},
}


def _toy_root(tmp_path):
    """A manifest of the same cells at a size the CPU holds in seconds."""
    root = tmp_path / "toy"
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir(parents=True)
    for c in MANIFEST["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        if cfg["name"] == "logreg_rows5m":
            cfg["dataset"]["n_samples"] = 12_000  # still past the Newton solver's row limit
        else:
            cfg["dataset"]["n_samples"] = 4_800
            cfg["estimator"]["params"].update(hidden_layer_sizes=[32, 32], max_iter=6)
            cfg["sensitive_trials"]["lowest"] = 2
        cfg["limits"].update(TOY_LIMITS[cfg["name"]])
        json.dump(cfg, open(root / c["file"], "w"))
    for t in ("rs128", "rs64"):
        traffic = json.load(open(os.path.join(BENCH, "traffic", t + ".json")))
        traffic.update(n_iter=6, check_trials=3)
        json.dump(traffic, open(root / "perfbench" / "traffic" / (t + ".json"), "w"))
    json.dump(MANIFEST, open(root / "BENCHMARK.json", "w"))
    return str(root)


def _run_toy(tmp_path, workload, trace=False, **kw):
    return run.run_cell(workload, 2**31 + 7, 0.5, trace, root=_toy_root(tmp_path),
                        require_tpu=False, state_dir=str(tmp_path / "state"), **kw)


@pytest.mark.parametrize("workload", ["logreg_rows5m.rs128", "mlp_mnist.rs64"])
def test_window_loop_and_last_line(tmp_path, workload):
    r, values = _run_toy(tmp_path, workload)
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    assert r["attempted"] == 6 * (1 + values["searches"]) and values["searches"] >= 1
    assert set(r["metrics"]) == {"trials_per_s", "first_search_s", "setup_s"}
    assert values["window_s"] >= 0.5
    assert r["metrics"]["trials_per_s"]["value"] == pytest.approx(6 * values["searches"] / values["window_s"])
    json.dumps(r), json.dumps(values)


def test_traced_run_reports_per_layer_metrics(tmp_path):
    r, _values = _run_toy(tmp_path, "logreg_rows5m.rs128", trace=True)
    assert r["correct"] is True
    # no device plane on the CPU: trace readers return nothing and are left out
    assert {"coord_host_ms", "engine_dispatch_ms", "engine_fetch_ms", "window_compiles",
            "stage_upload_s", "backend_compile_s"} <= set(r["metrics"])
    assert "logreg_step_roofline" not in r["metrics"] and "search_mfu" not in r["metrics"]
    assert r["metrics"]["window_compiles"]["value"] == 0


def test_a_trials_mesh_configuration_runs_on_four_host_devices(tmp_path):
    """``"mesh": "trials"`` in a configuration puts the search on a 1-D trial
    mesh over the cell's chips. No cell asks for it yet (PERF.md section 7,
    row 0), so the branch is driven here, on four forced host devices, in a
    process of its own (the device count is fixed when JAX starts)."""
    import subprocess

    root = _toy_root(tmp_path)
    cfg_path = os.path.join(root, "perfbench", "configs", "logreg_rows5m.json")
    cfg = json.load(open(cfg_path))
    cfg.update(mesh="trials", chips=4)
    json.dump(cfg, open(cfg_path, "w"))
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    next(w for w in manifest["workloads"] if w["name"] == "logreg_rows5m.rs128")["chips"] = 4
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    code = (
        "import importlib.util, json, sys\n"
        f"spec = importlib.util.spec_from_file_location('perfbench_run', {os.path.join(BENCH, 'run.py')!r})\n"
        "run = importlib.util.module_from_spec(spec); sys.modules['perfbench_run'] = run\n"
        "spec.loader.exec_module(run)\n"
        f"r, _ = run.run_cell('logreg_rows5m.rs128', 11, 0.5, False, root={root!r}, require_tpu=False)\n"
        "print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4 and r["failed"] == 0
    assert r["correct"] is True, r["compared"]


NOT_CORRECT = {  # what has to read not correct in the program's place (PERF.md section 6)
    "logreg_rows5m.rs128": ("control", "int8", "half_batch"),
    "mlp_mnist.rs64": ("control", "control.state_precision", "half_batch", "no_bias_correction"),
}


@pytest.mark.parametrize("workload", sorted(NOT_CORRECT))
def test_controls_and_faults_in_the_programs_place_read_not_correct(tmp_path, workload):
    """The plain reference in the precision below the stated one, and with
    each fault its family declares, put in the program's place and read by
    the comparison of a run: each has to come out not correct, and the
    stated precision correct."""
    import jax

    probe = run.load_module("tools/probe_limits.py")
    cell = run.load_cell(workload, _toy_root(tmp_path))
    row = probe.readings(cell, 5, True, jax.devices()[:1])
    assert row["program"]["correct"] is True, row["program"]
    assert row["bfloat16"]["correct"] is True, row["bfloat16"]
    family = run.load_module(f"references/{cell['config']['estimator']['class']}.py")
    assert set(family.FAULTS) <= set(NOT_CORRECT[workload])
    for name in NOT_CORRECT[workload]:
        assert row[name]["correct"] is False, (name, row[name])


def _patched(monkeypatch, fault, family):
    import jax.numpy as jnp

    from cs230_distributed_machine_learning_tpu.models import logistic, mlp
    from cs230_distributed_machine_learning_tpu.parallel import trial_map
    from cs230_distributed_machine_learning_tpu.runtime import coordinator

    trial_map._compiled_cache.clear()
    real_sub = coordinator.create_subtasks
    if fault == "answer_altered":
        real = trial_map._postprocess

        def altered(out, j, *a, **kw):
            out = dict(out, score=np.asarray(out["score"]) + np.float32(0.03))
            return real(out, j, *a, **kw)

        monkeypatch.setattr(trial_map, "_postprocess", altered)
    elif fault == "state_unchanged" and family == "logreg_rows5m":
        real_fit = logistic._nesterov

        def frozen(A, w, W0, *a, **kw):
            W, tr = real_fit(A, w, W0, *a, **kw)
            return W0 + 0.0 * W, tr

        monkeypatch.setattr(logistic, "_nesterov", frozen)
    elif fault == "state_unchanged":
        real_mlp = mlp._MLPBase._fit  # every step taken with a rate of nought

        def frozen_mlp(self, X, y, w, hyper, static, trace):
            return real_mlp(self, X, y, w, {**hyper, "learning_rate_init": 0.0}, static, trace)

        monkeypatch.setattr(mlp._MLPBase, "_fit", frozen_mlp)
    elif fault == "half_batch" and family == "logreg_rows5m":
        real_grad = logistic._make_masked_grad_fn

        def halved(A, Y, y, w, *a, **kw):  # every second row left out of the gradient
            return real_grad(A, Y, y, w * (jnp.arange(w.shape[0]) % 2 == 0), *a, **kw)

        monkeypatch.setattr(logistic, "_make_masked_grad_fn", halved)
    elif fault == "half_batch":
        real_mlp = mlp._MLPBase._fit  # every second row weighs nought, the mean over the rest

        def halved_mlp(self, X, y, w, hyper, static, trace):
            return real_mlp(self, X, y, w * (jnp.arange(w.shape[0]) % 2 == 0), hyper, static, trace)

        monkeypatch.setattr(mlp._MLPBase, "_fit", halved_mlp)
    elif fault == "trial_dropped":
        monkeypatch.setattr(coordinator, "create_subtasks",
                            lambda *a, **kw: real_sub(*a, **kw)[:-1])
    elif fault == "wrong_parameter":
        name = {"logreg_rows5m": "C", "mlp_mnist": "alpha"}[family]

        def shifted(*a, **kw):
            subs = real_sub(*a, **kw)
            for sub in subs:
                sub["parameters"][name] = sub["parameters"][name] * 1.5
            return subs

        monkeypatch.setattr(coordinator, "create_subtasks", shifted)


CAUGHT_BY = {  # the number meant to catch each fault, by configuration
    "answer_altered": {"logreg_rows5m": "score_gap_max", "mlp_mnist": "score_gap_sensitive"},
    "state_unchanged": {"logreg_rows5m": "score_gap_max", "mlp_mnist": "score_gap_mean"},
    "half_batch": {"logreg_rows5m": "curve_gap_vs_yardstick", "mlp_mnist": "score_gap_sensitive"},
    "trial_dropped": {"logreg_rows5m": "failed_trials", "mlp_mnist": "failed_trials"},
    "wrong_parameter": {"logreg_rows5m": "params_mismatch", "mlp_mnist": "params_mismatch"},
}


@pytest.mark.parametrize("workload", ["logreg_rows5m.rs128", "mlp_mnist.rs64"])
@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_a_broken_timed_path_reads_not_correct(tmp_path, monkeypatch, fault, workload):
    """The rest of a run with the timed path broken underneath: ``correct``
    has to come out false, through the number meant to catch the fault."""
    family = workload.split(".")[0]
    _patched(monkeypatch, fault, family)
    r, _values = _run_toy(tmp_path, workload)
    assert r["correct"] is False, r["compared"]
    over = [k for k, (v, lim) in r["compared"].items() if not v <= lim]
    assert CAUGHT_BY[fault][family] in over, r["compared"]
