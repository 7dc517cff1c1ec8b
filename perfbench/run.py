"""One cell of the benchmark, once: ``python3 perfbench/run.py --workload
<config>.<mix> --seed <n> --seconds <s> --trace <0|1>``.

A run makes the dataset from ``--seed``, builds the system as a user would
(``MLTaskManager`` over an in-process ``Coordinator`` and ``LocalExecutor``),
times the first ``train()`` of the process (staging, executable load or
compile, fit, fetch: the warm-up), then drives the same search in a closed
loop with one client until ``--seconds`` have passed, the window ending when
the search in flight returns. After the window it reads the device's memory
peak, frees the program's state, runs the plain reference over a sample of
the trials and decides ``correct``. The last line of stdout is one JSON
object. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.py``, ``references/<estimator class>.py``,
``work/<estimator class>.py``, ``datasets/<dataset kind>.py``,
``searches/<search kind>.py``.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
DATASET_ID = "perfbench"
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def load_module(relpath: str):
    """A file of the benchmark as a module, found by its path under
    ``perfbench/`` (no package, so nothing here can shadow or be shadowed)."""
    path = os.path.join(BENCH_DIR, relpath)
    name = "perfbench_" + relpath.replace("/", "_").removesuffix(".py")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = REPO_ROOT) -> Dict[str, Any]:
    """The manifest's entry for ``workload`` with its configuration and
    traffic files read in."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    wl = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == wl["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    tdir = os.path.join(root, os.path.dirname(os.path.dirname(cfg_entry["file"])), "traffic")
    with open(os.path.join(tdir, wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"name": workload, "chips": int(wl["chips"]), "config": config,
            "traffic": traffic, "manifest": manifest}


def cell_metrics(cell: Dict[str, Any], group: str) -> List[Dict[str, Any]]:
    return [m for m in cell["manifest"][group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def device_record(devices) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> Dict[str, int]:
    """Peak device memory of the fullest chip, as the runtime's allocator
    measured it. This runtime books an executable's scratch as *reserved*
    and not as *in use* (PERF.md section 6, PR 25), so the peak is the
    larger of the two peaks it keeps: a reading, and never less than either.
    (Their sum would be the peak only if both fell in the same moment, which
    nothing measures.)"""
    stats = [d.memory_stats() or {} for d in devices]
    in_use = max([int(s.get("peak_bytes_in_use", 0)) for s in stats] or [0])
    reserved = max([int(s.get("peak_bytes_reserved", 0)) for s in stats] or [0])
    return {"peak": max(in_use, reserved), "peak_bytes_in_use": in_use,
            "peak_bytes_reserved": reserved}


def make_dataset(cell: Dict[str, Any], seed: int):
    spec = cell["config"]["dataset"]
    return load_module("lib/datagen.py").make_dataset(
        spec, seed, load_module(f"datasets/{spec['kind']}.py").generate)


def search_kind(cell: Dict[str, Any]):
    return load_module(f"searches/{cell['traffic']['search']}.py")


def build_search(cell: Dict[str, Any], seed: int):
    """The sklearn search object a user would hand to ``train()``."""
    import importlib

    est = cell["config"]["estimator"]
    cls = getattr(importlib.import_module(est["module"]), est["class"])
    params = {k: (tuple(v) if isinstance(v, list) else v) for k, v in est["params"].items()}
    return search_kind(cell).build(cls(**params), cell["traffic"], seed)


class CompileWatch:
    """JAX's own compile telemetry, summed since registration."""

    def __init__(self):
        import jax.monitoring as mon

        self.seen = {"xla_compile_s": 0.0, "xla_compiles": 0, "pcache_hits": 0, "pcache_misses": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seen["xla_compile_s"] += duration
            self.seen["xla_compiles"] += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.seen["pcache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.seen["pcache_misses"] += 1


def read_counters(watch: CompileWatch) -> Dict[str, float]:
    """The program's counters the per-layer readers use, under honest
    names: the executor's ``device_seconds`` phases are host-clock walls."""
    from cs230_distributed_machine_learning_tpu.data import stage_cache
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    out = dict(watch.seen)
    for phase in ("compile", "stage", "dispatch", "fetch"):
        out[f"phase_{phase}_s"] = REGISTRY.counter(
            "tpuml_executor_device_seconds_total").value(phase=phase)
    out["exe_cache_hits"] = REGISTRY.counter("tpuml_executable_cache_hits_total").value()
    out["exe_cache_misses"] = REGISTRY.counter("tpuml_executable_cache_misses_total").value()
    stats = stage_cache.STAGE_CACHE.stats()
    out["stage_uploads"] = float(stats.get("uploads", 0))
    out["stage_host_upload_bytes"] = float(stats.get("host_upload_bytes", 0))
    return out


def build_system(cell: Dict[str, Any], X, y, devices):
    from cs230_distributed_machine_learning_tpu import MLTaskManager
    from cs230_distributed_machine_learning_tpu.runtime.coordinator import Coordinator
    from cs230_distributed_machine_learning_tpu.runtime.executor import LocalExecutor

    cache = load_module("lib/memcache.py").MemoryDatasetCache()
    cache.register(DATASET_ID, X, y, cell["config"]["dataset"]["n_classes"])
    mesh = None
    if cell["config"].get("mesh") == "trials":
        from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

        mesh = trial_mesh(devices[: cell["chips"]])
    coordinator = Coordinator(executor=LocalExecutor(mesh=mesh, cache=cache))
    coordinator.cache = cache
    return MLTaskManager(coordinator=coordinator), coordinator


def train_once(manager, search, cell) -> Dict[str, Any]:
    traffic = cell["traffic"]
    t0 = time.perf_counter()
    status = manager.train(
        search, DATASET_ID,
        {"random_state": int(traffic["split_random_state"]),
         "test_size": float(traffic["test_size"])},
        show_progress=False, timeout=900.0)
    t1 = time.perf_counter()
    return {"status": status, "wall_s": t1 - t0, "start": t0, "end": t1,
            "job_id": manager.job_id}


def free_program_state() -> None:
    """Drop what the program keeps on the device, so the reference (which
    runs once the peak has been read) has the chip to itself."""
    import jax

    from cs230_distributed_machine_learning_tpu.data import stage_cache

    stage_cache.STAGE_CACHE.clear()
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = REPO_ROOT, require_tpu: bool = True,
             state_dir: Optional[str] = None):
    """One run; returns the result object and, for standard error, the
    values it was made from (None when the device check fails).
    ``require_tpu=False`` and ``root`` exist for the CPU tests."""
    cell = load_cell(workload, root)
    import jax

    from cs230_distributed_machine_learning_tpu.utils.jax_setup import setup_jax

    setup_jax()
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"perfbench: needs {cell['chips']} TPU chip(s), found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return None
    devices = devices[: cell["chips"]]
    device = device_record(devices)
    watch = CompileWatch()
    compare = load_module("lib/compare.py")

    X, y = make_dataset(cell, seed)
    manager, coordinator = build_system(cell, X, y, devices)
    search = build_search(cell, seed)
    c_start = read_counters(watch)
    first = train_once(manager, search, cell)
    c_window = read_counters(watch)
    t_window = time.perf_counter()
    setup_s = t_window - _T_PROCESS

    searches, traced, trace_dir = [], None, None
    while True:
        if trace and traced is None:
            trace_dir = os.path.join(state_dir or os.path.join(root, ".perfbench_state"),
                                     "trace", workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("perfbench.search"):
                traced = train_once(manager, search, cell)
            jax.profiler.stop_trace()
            searches.append(traced)
        else:
            searches.append(train_once(manager, search, cell))
        if time.perf_counter() - t_window >= seconds:
            break
    window_s = time.perf_counter() - t_window
    c_end = read_counters(watch)
    memory = memory_peak_bytes(devices)
    device["memory_peak_bytes"] = memory["peak"]
    del manager, coordinator
    free_program_state()

    n_iter = int(cell["traffic"]["n_iter"])
    all_searches = [first] + searches
    failed = sum(compare.count_failed(s["status"], n_iter) for s in all_searches)
    reference = load_module(f"references/{cell['config']['estimator']['class']}.py")
    numbers, _detail = compare.compare(
        cell, search_kind(cell).expected(cell["traffic"], seed), seed, X, y,
        [s["status"] for s in all_searches], reference.reference)
    numbers["failed_trials"] = float(failed)
    correct, table = compare.judge(numbers, cell["config"]["limits"])

    trials_done = sum(n_iter - compare.count_failed(s["status"], n_iter) for s in searches)
    values = {"trials_per_s": trials_done / window_s, "first_search_s": first["wall_s"],
              "setup_s": setup_s}
    result: Dict[str, Any] = {"correct": correct, "attempted": n_iter * len(all_searches),
                              "failed": failed}
    if not trace:
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell_metrics(cell, "end_to_end")}
    else:
        tr, flops = load_module("lib/trace_reduce.py"), load_module("lib/flops.py")
        xplane = tr.find_xplane(trace_dir)
        reduced = tr.reduce_trace(tr.load(xplane), traced["wall_s"], cell["chips"]) if xplane else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = load_module("lib/peaks.py").peaks_for(device["kind"]) if require_tpu else None
        ctx = {"cell": cell, "chips": cell["chips"], "peaks": peaks, "trace": reduced,
               "traced_search": traced, "searches": searches, "first": first,
               "window_s": window_s,
               "counters": {"start": c_start, "window_start": c_window, "window_end": c_end},
               "memory_peak_bytes": device["memory_peak_bytes"],
               "work": load_module(f"work/{cell['config']['estimator']['class']}.py").search_work(cell, flops),
               "flops": flops, "trace_reduce": tr}
        metrics = {}
        for m in cell_metrics(cell, "per_layer"):
            value = load_module(f"layer_metrics/{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduced["device_ops"]],
                "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"]]}
    result["device"] = device
    result["compared"] = table  # each number compared beside its limit; the last key
    values = {**values, "window_s": window_s, "searches": len(searches),
              "search_walls_s": [s["wall_s"] for s in searches], "memory": memory,
              "counters": {k: c_end[k] - c_start[k] for k in c_end},
              "numbers": numbers}
    return result, values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if out is None:
        return 2
    result, values = out
    print("values " + json.dumps(values), file=sys.stderr)
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} = {value:.6g} (limit {limit:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
