"""CPU tests of the four-chip cell ``logreg_rows5m_mesh4.rs64c`` (PR 28): its
real configuration and traffic files at toy rows through the harness on four
host devices, its controls in the program's place, its four readers on
hand-made events and spans, and the mesh fit compiled at 5M rows for a
described v5e host with no chip attached.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_mesh4_cell.py -q

Nothing here is a measurement: a CPU run proves counts and control flow, and
a compile that passes is not a chip run.
"""

import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from test_perfbench import BENCH, ROOT, run  # noqa: E402 — the harness as the older tests load it

CELL, CONFIG, TRAFFIC = "logreg_rows5m_mesh4.rs64c", "logreg_rows5m_mesh4", "rs64c"


def _toy_root(tmp_path):
    """The cell's own files, rows and trials cut to what the CPU holds in
    seconds: 12 000 rows is still past the Newton solver's row limit, 6 trials
    on four devices leave two padding lanes."""
    root = tmp_path / "toy"
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir(parents=True)
    cfg = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    cfg["dataset"]["n_samples"] = 12_000
    cfg["limits"]["score_gap_max"] = 0.004  # one held-out row of 2 400 is 0.0004
    json.dump(cfg, open(root / "perfbench" / "configs" / (CONFIG + ".json"), "w"))
    traffic = json.load(open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")))
    traffic.update(n_iter=6, check_trials=3)
    json.dump(traffic, open(root / "perfbench" / "traffic" / (TRAFFIC + ".json"), "w"))
    json.dump(json.load(open(os.path.join(ROOT, "BENCHMARK.json"))), open(root / "BENCHMARK.json", "w"))
    return str(root)


def _on_four_host_devices(code, timeout=900):
    """The device count is fixed when JAX starts, so a process of its own."""
    prelude = (
        "import importlib.util, json, sys\n"
        f"spec = importlib.util.spec_from_file_location('perfbench_run', {os.path.join(BENCH, 'run.py')!r})\n"
        "run = importlib.util.module_from_spec(spec); sys.modules['perfbench_run'] = run\n"
        "spec.loader.exec_module(run)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", prelude + code], env=env, capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_cell_reads_correct_at_toy_rows_on_four_host_devices(tmp_path):
    root, state = _toy_root(tmp_path), str(tmp_path / "state")
    r = _on_four_host_devices(
        f"r, v = run.run_cell({CELL!r}, 2**31 + 11, 0.5, True, root={root!r}, require_tpu=False,"
        f" state_dir={state!r})\n"
        "print(json.dumps({'r': r, 'searches': v['searches']}))\n")
    res = r["r"]
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert res["device"]["count"] == 4 and res["attempted"] == 6 * (1 + r["searches"])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # 6 trials in one chunk of 8 on four devices: one dispatch, a quarter padding
    assert m["mesh_dispatches"] == 1 and m["mesh_pad_lanes_pct"] == pytest.approx(25.0)
    assert m["window_compiles"] == 0 and m["warm_stage_mb"] == 0 and m["split_plan_ms"] < 50
    # no device plane on the CPU: the two trace readers find nothing
    assert "mesh_chip_skew_pct" not in m and "mesh_collective_ms" not in m


def test_controls_and_the_fault_in_the_programs_place_read_not_correct(tmp_path):
    """The plain reference in the precision below the stated one, in int8 and
    with half the batch left out, put in the mesh program's place and read by
    the comparison of a run: each not correct, the stated precision correct."""
    root = _toy_root(tmp_path)
    row = _on_four_host_devices(
        "import jax\n"
        "probe = run.load_module('tools/probe_limits.py')\n"
        f"cell = run.load_cell({CELL!r}, {root!r})\n"
        "print(json.dumps(probe.readings(cell, 5, True, jax.devices()[:4])))\n")
    assert row["program"]["correct"] is True, row["program"]
    assert row["bfloat16"]["correct"] is True, row["bfloat16"]
    for name in ("control", "int8", "half_batch"):
        assert row[name]["correct"] is False, (name, row[name])


# ------------------------------------------------------------ the readers

def _reader(name):
    return run.load_module(f"layer_metrics/{name}.py")


def test_trace_readers_on_hand_made_events():
    tr = run.load_module("lib/trace_reduce.py")
    ms = 1e6  # events are in nanoseconds
    fit = "%fusion.49 = f32[4,6,55,7] fusion(...)"
    gather = "%all-gather.3 = f32[16,6] all-gather(%score), replica_groups={{0,1,2,3}}"
    reduce_ = "%all-reduce.1 = f32[] all-reduce(%max)"
    trace = {"devices": {0: [(0, 100 * ms, fit), (100 * ms, 103 * ms, gather), (103 * ms, 104 * ms, reduce_)],
                         1: [(0, 90 * ms, fit), (100 * ms, 102 * ms, gather)],
                         2: [(0, 95 * ms, fit)], 3: [(0, 99 * ms, fit)]},
             "labels": {}, "host": []}
    ctx = {"trace": tr.reduce_trace(trace, 0.2, 4), "trace_reduce": tr}
    # fullest 104 ms, emptiest 92 ms
    assert _reader("mesh_chip_skew_pct").read(ctx) == pytest.approx(100 * 12 / 104)
    assert _reader("mesh_collective_ms").read(ctx) == pytest.approx(4.0)
    # a mesh search in which XLA placed no collective reads 0, not nothing
    quiet = {**trace, "devices": {i: [(0, 90 * ms, fit)] for i in range(4)}}
    ctx = {"trace": tr.reduce_trace(quiet, 0.2, 4), "trace_reduce": tr}
    assert _reader("mesh_collective_ms").read(ctx) == 0.0
    assert _reader("mesh_chip_skew_pct").read(ctx) == 0.0
    # one traced device (the one-chip cells), or no trace: nothing to read
    one = {"trace": tr.reduce_trace({**trace, "devices": {0: trace["devices"][0]}}, 0.2, 1),
           "trace_reduce": tr}
    for name in ("mesh_chip_skew_pct", "mesh_collective_ms"):
        assert _reader(name).read(one) is None
        assert _reader(name).read({"trace": None, "trace_reduce": tr}) is None


def test_span_readers_on_hand_made_spans():
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    t = 1_790_000_000.0

    def search(job, tid, dispatches):
        TRACER.bind_job(job, tid)
        for i, attrs in enumerate(dispatches):
            TRACER.record({"trace_id": tid, "span_id": f"d{i}", "parent_id": None,
                           "name": "executor.dispatch", "start": t + i, "end": t + i + 0.01,
                           "attrs": attrs, "process": "pid:1"})

    lanes = lambda n, pad: {"chunk": 0, "n_trials": n - pad, "n_devices": 4, "lanes": n, "lanes_padding": pad}
    search("job-mesh-a", "me5h00000000000a", [lanes(16, 0)] * 3 + [lanes(16, 8)])
    search("job-mesh-b", "me5h00000000000b", [lanes(16, 0)] * 3 + [lanes(16, 8)])
    ctx = {"searches": [{"job_id": "job-mesh-a"}, {"job_id": "job-mesh-b"}]}
    assert _reader("mesh_dispatches").read(ctx) == 4.0
    assert _reader("mesh_pad_lanes_pct").read(ctx) == pytest.approx(100 * 16 / 128)
    # the parent commit's dispatch spans say chunk and n_trials only: the
    # count is there to read, the lanes are not
    search("job-old-mesh", "me5h00000000000c", [{"chunk": i, "n_trials": 16} for i in range(4)])
    old = {"searches": [{"job_id": "job-old-mesh"}]}
    assert _reader("mesh_dispatches").read(old) == 4.0
    assert _reader("mesh_pad_lanes_pct").read(old) is None
    nothing = {"searches": [{"job_id": "never-traced"}]}
    assert _reader("mesh_dispatches").read(nothing) is None
    assert _reader("mesh_pad_lanes_pct").read(nothing) is None


def test_work_of_the_cell_is_half_of_rs128s():
    """64 trials, not 128, of the same fits: ``search_mfu`` divides this by
    four chips' peak, so it cannot read high."""
    flops = run.load_module("lib/flops.py")
    work = run.load_module("work/LogisticRegression.py").search_work
    mesh, one = work(run.load_cell(CELL), flops), work(run.load_cell("logreg_rows5m.rs128"), flops)
    for k in ("fit_flops", "score_flops"):
        assert mesh[k] == pytest.approx(one[k] / 2)
    # by hand: 100 steps x 64 trials x 2 matmuls x 2 FLOPs x 55 x 7 x training rows of six splits
    assert mesh["fit_flops"] == pytest.approx(100 * 64 * 4 * 55 * 7 * (4_000_000 + 5 * 4_000_000))


# ------------------------------------------- the mesh fit, compiled for a v5e host

@pytest.fixture(scope="module")
def v5e_host():
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices[:4]), ("trials",))


def test_logreg_rows5m_mesh_fit_compiles_for_four_chips(v5e_host, monkeypatch):
    """n = 5M, the chunk of 16 the memory cap picks on four 16.9 GB chips, the
    engine's own mesh executable (``_xla_only(_make_batched(...))`` under its
    in- and out-shardings): no Mosaic call, no collective inside the fit, and
    each chip's arguments and temporaries fit beside each other."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # `auto` valves decide as on the chip
    for valve in ("CS230_PALLAS_INTERPRET", "CS230_FUSED_STEP", "CS230_MASKED_GRAD"):
        monkeypatch.delenv(valve, raising=False)
    cell = run.load_cell(CELL)
    ds, est = cell["config"]["dataset"], cell["config"]["estimator"]
    n, d, c = ds["n_samples"], ds["n_features"], ds["n_classes"]
    S, chips = cell["traffic"]["cv"] + 1, cell["chips"]
    kernel = get_kernel(est["class"])
    static = kernel.resolve_static(kernel.static_from_key(kernel.canonicalize(est["params"])[0]), n, d, c)
    static["_n_classes"] = c
    static = kernel.bucket_static(static, [est["params"]])
    assert static["_method"] == "nesterov" and static["_iters"] == 100
    # the chunk the engine picks: half of four chips' memory over the kernel's estimate
    monkeypatch.setattr(trial_map._backend, "device_memory_mb", lambda: 16909334528 / 1e6)
    cap = trial_map._memory_chunk_cap(kernel, n, d, static, S, chips)
    chunk = max(chips, -(-min(256, cap, cell["traffic"]["n_iter"]) // chips) * chips)
    assert chunk == 16 and cell["traffic"]["n_iter"] % chunk == 0  # four dispatches, no padding lane

    repl, sharded = NamedSharding(v5e_host, P()), NamedSharding(v5e_host, P("trials"))
    fn = jax.jit(trial_map._xla_only(trial_map._make_batched(kernel, static, True)),
                 in_shardings=(repl, repl, repl, repl, sharded), out_shardings=sharded)
    sds = lambda shape, dt, sh: jax.ShapeDtypeStruct(tuple(shape), dt, sharding=sh)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.lower(
            sds((n, d), jnp.float32, repl), sds((n,), jnp.int32, repl),
            sds((S, n), jnp.float32, repl), sds((S, n), jnp.float32, repl),
            {h: sds((chunk,), jnp.float32, sharded) for h in ("C", "max_iter", "tol")}).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # the XLA formulation, as `_xla_only` says
    assert not any(op in text for op in ("all-reduce", "all-gather", "reduce-scatter",
                                         "collective-permute", "all-to-all"))
    mem = compiled.memory_analysis()  # of one chip
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.5e9
    assert mem.temp_size_in_bytes > 4.3e9  # a quarter of a chip: a smaller cell is refused
