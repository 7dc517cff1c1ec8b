"""TPU compile gate, run on the CPU: every Pallas entry point an ``auto``
valve can select on a TPU backend is lowered for ``platforms=("tpu",)`` at
its default geometry and, where libtpu can describe a v5e topology with no
chip attached, compiled by the real TPU compiler (Mosaic included).

Interpret-mode parity tests cannot see what this file sees: block shapes
the TPU lowering refuses, kernels over the scoped-VMEM limit, Mosaic calls
under mesh shardings. A deviceless compile says the compiler accepts the
program — not that it runs, is correct, or fits HBM; ``chip_smoke.py``
covers that on the chip.
"""

import functools
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from cs230_distributed_machine_learning_tpu.models.registry import get_kernel

#: the flagship's geometry: covertype, cv=5 -> 6 splits
N, D, C, S = 116_202, 54, 7, 6


@functools.lru_cache(maxsize=1)
def _v5e_devices():
    """Four deviceless ``TPU v5 lite`` devices, or None with the reason."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        return tuple(topo.devices), None
    except Exception as e:  # noqa: BLE001 — no libtpu: lowering-only run
        return None, f"no deviceless TPU topology here: {e!r}"


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the package's ``auto`` valves decide as they do on a TPU
    backend (they ask ``jax.default_backend()`` through utils/backend)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("CS230_PALLAS_INTERPRET", raising=False)
    for valve in ("CS230_FUSED_STEP", "CS230_MASKED_GRAD", "CS230_HIST_KERNEL"):
        monkeypatch.delenv(valve, raising=False)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _lower_and_compile(fn, *args):
    """Compile ``fn`` for one v5e device where a deviceless topology is
    available (which lowers it on the way); lower it for the TPU platform
    where not."""
    devices, _ = _v5e_devices()
    if devices is None:
        jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
        return None
    sh = SingleDeviceSharding(devices[0])
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args
    )
    return jax.jit(fn).lower(*placed).compile()


def _logreg_static():
    kernel = get_kernel("LogisticRegression")
    static = kernel.resolve_static(
        {"fit_intercept": True, "penalty": "l2"}, N, D, C
    )
    static["_n_classes"] = C
    return kernel, kernel.bucket_static(static, [{"max_iter": 200}])


def _trial_args(n, d, s, chunk, hyper_names):
    return (
        _sds((n, d), jnp.float32), _sds((n,), jnp.int32),
        _sds((s, n), jnp.float32), _sds((s, n), jnp.float32),
        {h: _sds((chunk,), jnp.float32) for h in hyper_names},
    )


@pytest.mark.parametrize("n,chunk", [(N, 128), (N, 1024), (12_000, 1024)])
def test_flagship_packed_fit(tpu_backend, n, chunk):
    """bench.py's search: 1000 trials -> one 1024-trial chunk (n_wb=8);
    the REST slice and every test shape use 128 (n_wb=1). At small n XLA
    parks the whole design matrix in VMEM next to the fused step, which
    needs the call's raised scoped-VMEM limit to compile."""
    kernel, static = _logreg_static()
    assert kernel.batched_applicable(static, n, D)
    fn = kernel.build_batched_fn(static, n, D, C, S, chunk)
    _lower_and_compile(
        fn, *_trial_args(n, D, S, chunk, ("C", "max_iter", "tol"))
    )


def _trial_blocks():
    """The trial-block widths the packed fit chooses among: every one runs,
    its class slabs padded to whole vregs (a constant: safe while a test
    file is imported)."""
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        TRIAL_BLOCKS,
    )

    return list(TRIAL_BLOCKS)


def _fused_step_compiled(Tw, n_wb, n_pad, table):
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        packed_nesterov_step, slab_lanes,
    )

    dpp, bm = 64, 256
    B = slab_lanes(S, Tw)
    W = _sds((n_wb, dpp, C * B), jnp.float32)
    col = _sds((n_wb, B), jnp.float32)
    occ = (_sds((n_pad // bm,), jnp.int32),) if table else ()
    return _lower_and_compile(
        functools.partial(packed_nesterov_step, c=C, S=S, Tw=Tw, bm=bm, lam=1.0),
        _sds((n_pad, dpp), jnp.bfloat16), W, W, _sds((n_pad, 1), jnp.int32),
        _sds((n_pad, S), jnp.float32), _sds((), jnp.float32),
        col, col, col, col, _sds((dpp, 1), jnp.float32), *occ,
    )


@pytest.mark.parametrize("Tw", _trial_blocks())
def test_fused_step_kernel(tpu_backend, Tw):
    """Every width with the occupancy table handed in, as the packed fit
    hands it: a block of 128 takes it as a scalar-prefetch operand and
    holds the whole-slab body and the slab less an empty split's column
    group, the narrower blocks ignore it and compile the whole-slab body
    alone; all inside ``_FUSED_STEP_VMEM_LIMIT``."""
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        tile_skip_applicable,
    )

    compiled = _fused_step_compiled(Tw, n_wb=8, n_pad=2048, table=True)
    assert tile_skip_applicable(S, Tw) == (Tw == 128)
    if compiled is not None:
        # the table is an operand of the custom call only where it is read
        call = re.search(
            r"packed_nesterov_step\S* = .*?custom-call\(([^)]*)\)",
            compiled.as_text(),
        )
        assert call is not None
        assert len(call.group(1).split(",")) == (12 if Tw == 128 else 11)


@pytest.mark.parametrize("table", [False, True])
def test_fused_step_kernel_at_the_cell_rows(tpu_backend, table):
    """The benchmark cell's row tiles (5M rows: 19 536 words of table, 78
    KB of SMEM) at one block of 128, with the table and without it (the
    unskipped parity reference of the chip check)."""
    _fused_step_compiled(128, n_wb=1, n_pad=5_001_216, table=table)


@pytest.mark.parametrize("vmapped", [False, True])
def test_masked_softmax_grad(tpu_backend, vmapped):
    """The generic LogReg fit's lane kernel, alone and under the engine's
    trials x splits vmap (which adds grid dimensions)."""
    from cs230_distributed_machine_learning_tpu.ops.pallas_logreg import (
        masked_softmax_grad,
    )

    n_pad, dpp, cp = 116_224, 128, 128
    Ab = _sds((n_pad, dpp), jnp.bfloat16)
    y2 = _sds((n_pad, 1), jnp.int32)
    one = functools.partial(masked_softmax_grad, c=C)
    if not vmapped:
        _lower_and_compile(
            one, Ab, _sds((dpp, cp), jnp.bfloat16), y2,
            _sds((n_pad, 1), jnp.float32),
        )
        return
    over_splits = jax.vmap(one, in_axes=(None, 0, None, 0))
    over_trials = jax.vmap(over_splits, in_axes=(None, 0, None, None))
    _lower_and_compile(
        over_trials, Ab, _sds((4, S, dpp, cp), jnp.bfloat16), y2,
        _sds((S, n_pad, 1), jnp.float32),
    )


@pytest.mark.parametrize("n_nodes", [8, 512])
@pytest.mark.parametrize("n_bins", [17, 32, 64])
def test_hist_gate_admits_only_what_compiles(tpu_backend, n_bins, n_nodes):
    """Covertype forests, 7 integer stat columns: whatever shape the
    kernel's gate admits must get through the compiler. 64 bins needs
    18.4 MB of scoped VMEM at a 64-node block against a 16 MB limit. The
    ``auto`` route takes the matmul form on a TPU since PR 32 (the kernel
    lost to it by 3.4 times over a forest search on the v5e) and the
    kernel runs behind ``CS230_HIST_KERNEL=pallas``."""
    from cs230_distributed_machine_learning_tpu.ops.pallas_hist import (
        level_histogram_pallas, pallas_hist_applicable,
    )
    from cs230_distributed_machine_learning_tpu.ops.trees import (
        _resolve_hist_kernel,
    )

    kk, n = 7, 20_000
    assert _resolve_hist_kernel(True, (D,), (n_bins,), kk) == "matmul"
    if not pallas_hist_applicable(D, n_bins, kk):
        return

    def hist(local, xb, SC):
        return level_histogram_pallas(
            local, xb, SC, n_nodes, n_bins, integer_stats=True
        )

    _lower_and_compile(
        hist, _sds((n,), jnp.int32), _sds((n, D), jnp.int32),
        _sds((n, kk), jnp.float32),
    )


def test_knn_topk(tpu_backend):
    from cs230_distributed_machine_learning_tpu.models.knn import _use_pallas
    from cs230_distributed_machine_learning_tpu.ops.pallas_knn import knn_topk

    n = 160_000
    assert _use_pallas(n)
    _lower_and_compile(
        functools.partial(knn_topk, k=5),
        _sds((1024, D), jnp.float32), _sds((n, D), jnp.float32),
        _sds((n,), jnp.float32),
    )


def _mlp_batched(chunk=4):
    kernel = get_kernel("MLPClassifier")
    n, d, c = 60_000, 784, 10
    _, hyper = kernel.canonicalize({"hidden_layer_sizes": (256,)})
    static = kernel.resolve_static(
        {**kernel.static_defaults, "hidden_layer_sizes": (256,),
         "max_iter": 2}, n, d, c,
    )
    static["_n_classes"] = c
    assert kernel.batched_applicable(static, n, d)
    fn = kernel.build_batched_fn(static, n, d, c, S, chunk)
    return fn, _trial_args(n, d, S, chunk, sorted(hyper))


def test_mlp_epoch_kernel(tpu_backend):
    """The fused MLP path at MNIST width (784-256-10), through the
    kernel's own builder so lane packing and the VMEM limit are the
    production ones."""
    fn, args = _mlp_batched()
    _lower_and_compile(fn, *args)


@pytest.mark.parametrize("data_parallel", [1, 2])
def test_sharded_generic_logreg_four_devices(tpu_backend, data_parallel):
    """The mesh executable of the flagship search (bench.py on a four-chip
    host, chip_smoke.py's mesh leg): ``jit`` with mesh shardings cannot
    partition a Mosaic kernel, so the engine must trace it on the XLA
    formulations — on the 1-D trial mesh and the 2-D (trials, data) mesh."""
    devices, why = _v5e_devices()
    if devices is None:
        pytest.skip(why)
    from cs230_distributed_machine_learning_tpu.models.base import TrialData
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    shape = (4 // data_parallel, data_parallel)
    names = ("trials", "data")
    if data_parallel == 1:
        shape, names = shape[:1], names[:1]
    mesh = Mesh(np.array(devices).reshape(shape), names)
    kernel, static = _logreg_static()
    n = 116_224 if data_parallel > 1 else N  # rows divisible by the data axis
    data = TrialData(
        X=np.zeros((n, D), np.float32), y=np.zeros((n,), np.int32),
        n_classes=C,
    )

    class Plan:
        n_splits = S

    chunk = 64
    args = _trial_args(n, D, S, chunk, ("C", "max_iter", "tol"))
    fn, _, _, fresh = trial_map._get_compiled(
        kernel, ("tpu-compile-test", data_parallel), static, mesh, "trials",
        data, Plan, chunk, ["C", "max_iter", "tol"], args[0],
    )
    assert fresh
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo  # no Mosaic kernel under the mesh
    if data_parallel == 1:
        # the trial axis needs no communication
        assert "all-reduce" not in hlo and "all-gather" not in hlo


@pytest.mark.parametrize("Tw", _trial_blocks())
def test_sharded_packed_logreg_four_devices(tpu_backend, Tw):
    """The four-chip benchmark cell's executable (``logreg_rows5m_mesh4``:
    5M x 54, 7 classes, 6 splits, 100 steps, staged extras handed in) at
    every block a device's share may get: the packed fit under
    ``shard_map`` keeps its one Mosaic call and puts nothing across chips
    inside the fit; dealing the lanes adds only the two gathers outside it."""
    devices, why = _v5e_devices()
    if devices is None:
        pytest.skip(why)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    n, steps = 5_000_000, 100
    kernel = get_kernel("LogisticRegression")
    static = kernel.resolve_static({"fit_intercept": True, "penalty": "l2"}, n, D, C)
    static["_n_classes"] = C
    static = kernel.bucket_static(static, [{"max_iter": steps}])
    assert kernel.batched_trial_block(Tw, S) == Tw
    fn = kernel.build_batched_fn(static, n, D, C, S, Tw)
    mesh = Mesh(np.array(devices), ("trials",))
    repl, sharded = NamedSharding(mesh, P()), NamedSharding(mesh, P("trials"))
    trial_keys = ("C", "max_iter", "tol")
    n_pad = -(-n // 2048) * 2048
    sds = lambda shape, dt, sh: jax.ShapeDtypeStruct(tuple(shape), dt, sharding=sh)  # noqa: E731
    args = (
        sds((n, D), jnp.float32, repl), sds((n,), jnp.int32, repl),
        sds((S, n), jnp.float32, repl), sds((S, n), jnp.float32, repl),
        {**{h: sds((4 * Tw,), jnp.float32, sharded) for h in trial_keys},
         "_logreg_ab": sds((n_pad, 64), jnp.bfloat16, repl),
         "_logreg_lam_max": sds((S,), jnp.float32, repl)},
    )
    collectives = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        fit = jax.jit(
            trial_map._shard_map_trials(fn, mesh, "trials", frozenset(trial_keys)),
            out_shardings=sharded,
        ).lower(*args).compile()
        dealt = trial_map._shard_batched(
            fn, mesh, "trials", Tw, trial_keys, ("_logreg_ab", "_logreg_lam_max"),
        ).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = fit.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 1
    assert "packed_nesterov_step" in text
    assert not any(op in text for op in collectives)
    mem = fit.memory_analysis()  # of one chip
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.5e9
    # the executable the engine runs: the same one kernel, and what crosses
    # chips is the dealing of [chunk] hypers and [chunk, S(, slots)] results
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', dealt.as_text())) == 1


def test_host_fast_path_traces_cpu_formulations(tpu_backend):
    """A bucket under the host-exec MAC line runs on the host CPU of an
    accelerator process. Its program must be traced for the CPU: on the
    chip a tiny forest traced the Pallas histogram (the default backend
    said "tpu") and died in the CPU lowering."""
    from cs230_distributed_machine_learning_tpu.models.base import TrialData
    from cs230_distributed_machine_learning_tpu.ops.folds import (
        build_split_plan,
    )
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    rng = np.random.RandomState(1)
    X = rng.randn(96, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.randn(96) > 0).astype(np.int32)
    data = TrialData(X=X, y=y, n_classes=2)
    plan = build_split_plan(y, task="classification", n_folds=3)
    trial_map._compiled_cache.clear()
    out = trial_map.run_trials(
        get_kernel("RandomForestClassifier"), data, plan,
        [{"n_estimators": 8, "max_depth": 3, "random_state": 0}],
    )
    assert len(out.trial_metrics) == 1
    assert np.isfinite(out.trial_metrics[0]["mean_cv_score"])
    kinds = {k[0] for k in trial_map._compiled_cache if isinstance(k, tuple)}
    assert "host" in kinds  # it did take the host fast path


def _logreg_batched(chunk=128):
    kernel, static = _logreg_static()
    fn = kernel.build_batched_fn(static, N, D, C, S, chunk)
    return fn, _trial_args(N, D, S, chunk, ("C", "max_iter", "tol"))


@pytest.mark.parametrize("family,build,kernel_name", [
    ("LogisticRegression", _logreg_batched, "packed_nesterov_step"),
    ("MLPClassifier", _mlp_batched, "mlp_fused_epoch"),
])
def test_batched_functions_carry_scopes_and_kernel_names(
    tpu_backend, family, build, kernel_name
):
    """What a profiler trace shows of a family's executable: every op under
    ``tpuml.fit``, ``tpuml.eval`` or ``tpuml.pack`` (the op's label), the
    Mosaic kernel under its own name (the op's name). Lowering only: names
    are metadata, and the CPU lowering would drop the kernel's."""
    from cs230_distributed_machine_learning_tpu.parallel import packing

    fn, args = build()
    text = (
        jax.jit(packing.pack_wrap(fn)).trace(*args)
        .lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    )
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, flags=re.M))
    scoped = [n for n in names.values() if n.startswith("jit(packed)/")]
    for scope in ("tpuml.fit", "tpuml.eval", "tpuml.pack"):
        assert any(f"/{scope}/" in n for n in scoped), (family, scope)
    # nothing the executable computes is left outside the three scopes
    assert not [n for n in scoped if "/tpuml." not in n]
    (call,) = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    assert f'kernel_name = "{kernel_name}"' in call
    # compiled for the chip, the kernel's instruction has the kernel's name
    # (a trace event's name) and the fit's scope in its op_name (its label)
    compiled = _lower_and_compile(packing.pack_wrap(fn), *args)
    if compiled is not None:
        (instr,) = {m.group(0) for m in re.finditer(
            rf'%{kernel_name}[\w.]* = [^\n]*custom_call_target="tpu_custom_call"[^\n]*',
            compiled.as_text())}
        assert "/tpuml.fit/" in re.search(r'op_name="([^"]*)"', instr).group(1)


@pytest.mark.parametrize("name", [
    "packed_softmax_grad", "masked_softmax_grad", "knn_topk", "level_histogram",
])
def test_every_other_pallas_call_has_its_name(tpu_backend, name):
    from cs230_distributed_machine_learning_tpu.ops import (
        pallas_hist, pallas_knn, pallas_logreg,
    )

    n_pad, dpp, Tw = 2048, 64, 128
    lowered = {
        "packed_softmax_grad": lambda: jax.jit(functools.partial(
            pallas_logreg.packed_softmax_grad, c=C, S=S, Tw=Tw)).trace(
            _sds((n_pad, dpp), jnp.bfloat16), _sds((1, dpp, C * S * Tw), jnp.bfloat16),
            _sds((n_pad, 1), jnp.int32), _sds((n_pad, S), jnp.float32)),
        "masked_softmax_grad": lambda: jax.jit(functools.partial(
            pallas_logreg.masked_softmax_grad, c=C)).trace(
            _sds((n_pad, 128), jnp.bfloat16), _sds((128, 128), jnp.bfloat16),
            _sds((n_pad, 1), jnp.int32), _sds((n_pad, 1), jnp.float32)),
        "knn_topk": lambda: jax.jit(functools.partial(pallas_knn.knn_topk, k=5)).trace(
            _sds((1024, D), jnp.float32), _sds((4096, D), jnp.float32),
            _sds((4096,), jnp.float32)),
        "level_histogram": lambda: jax.jit(lambda a, b, c_: pallas_hist.level_histogram_pallas(
            a, b, c_, 8, 17, integer_stats=True)).trace(
            _sds((4096,), jnp.int32), _sds((4096, D), jnp.int32), _sds((4096, 7), jnp.float32)),
    }[name]()
    text = lowered.lower(lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{name}"' in text


# ---------------------------------------------------------------------------
# the forest cell (rf_covertype.rs4, PR 32): one chunk of the chunked
# protocol at the cell's shape. The whole step program takes the TPU compiler
# a minute and more here (three with its 24 levels unrolled, before the deep
# builder's level plan), so this file lowers it for the TPU and reads the
# level plan off the trace; the Pallas histogram, which the `auto` route no
# longer takes, is compiled alone at the shapes the step would give it.
# ---------------------------------------------------------------------------

_FOREST_PARAMS = {"n_estimators": 4, "max_depth": None, "bootstrap": True,
                  "random_state": 0, "max_features": "sqrt", "min_samples_leaf": 1}
#: Covertype's column kinds as prepare_data groups them, and the cell's rows
#: (two fifths of the source's 581 012)
_D_CONT, _D_COARSE, _N_FOREST = 10, 44, 232_405


def _forest_step():
    """(vstep, its example arguments, kernel, static, X) as
    ``trial_map._run_chunked`` builds them for one trial of the cell."""
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    kernel = get_kernel("RandomForestClassifier")
    static_key, _ = kernel.canonicalize(_FOREST_PARAMS)
    static = trial_map._resolved_static(kernel, static_key, _N_FOREST, D, C)
    X = {"X": _sds((_N_FOREST, D), jnp.float32), "xb": _sds((_N_FOREST, D), jnp.int32),
         "edges": _sds((D, 47), jnp.float32),
         "xb_cont": _sds((_N_FOREST, _D_CONT), jnp.int32), "xb_coarse": _sds((_N_FOREST, _D_COARSE), jnp.int32),
         "fid_cont": _sds((_D_CONT,), jnp.int32), "fid_coarse": _sds((_D_COARSE,), jnp.int32)}
    plan = kernel.chunked_plan(static, _N_FOREST, D, C, S, prepared=X)

    def step_b(X, y, TW, EW, hyper, ci, state):
        return jax.vmap(lambda tw, st: kernel.chunk_step(
            X, y, tw, {}, static, ci, st, plan))(TW, state)

    vstep = jax.vmap(step_b, in_axes=(None, None, None, None, 0, None, 0))
    args = (X, _sds((_N_FOREST,), jnp.int32), _sds((S, _N_FOREST), jnp.float32),
            _sds((S, _N_FOREST), jnp.float32),
            {"_pad": _sds((1,), jnp.float32)}, _sds((), jnp.int32),
            _sds((1, S, _N_FOREST, C), jnp.float32))
    return vstep, args, kernel, static, X, plan


def test_forest_chunked_step_lowers_at_the_cell_shape(tpu_backend, monkeypatch):
    from cs230_distributed_machine_learning_tpu.ops import trees as ops_trees

    vstep, args, kernel, static, X, plan = _forest_step()
    assert plan == {"n_chunks": 4, "trees_per_chunk": 1}
    assert (static["_W"], static["_levels"], static["_wsched"]) == (1536, 24, (1536, 17, 512))
    seen, resolve = [], ops_trees._resolve_hist_kernel

    def spy(integer_stats, ds, n_binss, kk):
        seen.append((tuple(ds), tuple(n_binss), kk, resolve(integer_stats, ds, n_binss, kk)))
        return seen[-1][-1]

    monkeypatch.setattr(ops_trees, "_resolve_hist_kernel", spy)
    text = jax.jit(vstep).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    # the level plan: 24 histograms from 9 traced bodies. The root, levels
    # 0-5 as one scan over 64 slots and level 6 (64 -> 512 slots) at 48 bins;
    # levels 7, 8, 9 (512 slots, 9 handing 1536 on), levels 10-15 as one scan
    # over 1536, level 16 (1536 -> 512) and levels 17-22 as one scan over 512
    # at 16; the coarse group at 4
    assert seen == ([((_D_CONT, _D_COARSE), (48, 4), C, "matmul")] * 3
                    + [((_D_CONT, _D_COARSE), (16, 4), C, "matmul")] * 6)
    assert text.count("stablehlo.while") >= 9 + 3  # a row scan each, and the three level scans
    assert "level_histogram" in jax.jit(vstep).trace(*args).jaxpr.pretty_print(name_stack=True)
    # what the dispatch span says of the fit is what the trace resolved
    monkeypatch.setattr(ops_trees, "_resolve_hist_kernel", resolve)
    assert kernel.dispatch_attrs(static, X) == {
        "levels": 24, "arena_width": 1536, "hist_route": "matmul",
        "hist_levels_by_route": "matmul:24"}


@pytest.mark.parametrize("n_nodes,d,n_bins", [
    (1536, _D_CONT, 16), (1536, _D_COARSE, 4), (64, _D_CONT, 48), (512, _D_CONT, 16)])
def test_forest_level_histograms_compile_under_six_lanes(tpu_backend, n_nodes, d, n_bins):
    """The cell's level histograms as the Pallas kernel takes them behind
    ``CS230_HIST_KERNEL=pallas``: one Mosaic call over the six split lanes
    (the lane axis becomes a grid axis), at the level plan's slot counts and
    both resolutions."""
    from cs230_distributed_machine_learning_tpu.ops.pallas_hist import (
        level_histogram_pallas, pallas_hist_applicable,
    )

    assert pallas_hist_applicable(d, n_bins, C)

    def lanes(local, xb, SC):
        return jax.vmap(lambda lo, sc: level_histogram_pallas(
            lo, xb, sc, n_nodes, n_bins, integer_stats=True))(local, SC)

    _lower_and_compile(lanes, _sds((S, _N_FOREST), jnp.int32), _sds((_N_FOREST, d), jnp.int32),
                       _sds((S, _N_FOREST, C), jnp.float32))


def _perfbench_cell(estimator, config, traffic):
    """A cell as the benchmark's readers see it, and its work file, loaded
    by path and only read."""
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perfbench")
    spec = importlib.util.spec_from_file_location(
        f"_perfbench_work_{estimator}", os.path.join(root, "work", estimator + ".py"))
    work = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(work)
    with open(os.path.join(root, "configs", config + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "traffic", traffic + ".json")) as f:
        traffic = json.load(f)
    return work, {"config": config, "traffic": traffic}


def _perfbench_forest_cell():
    return _perfbench_cell("RandomForestClassifier", "rf_covertype", "rs4")


@pytest.mark.parametrize("n_nodes,n_bins", [(1536, 16), (512, 16), (64, 48)])
def test_forest_matmul_histogram_row_loop_has_no_retiling(tpu_backend, n_nodes, n_bins):
    """The form every histogram of the forest cell runs (``auto`` on a TPU):
    the XLA s8 matmul under six split lanes, both feature groups in one row
    scan, at the level plan's slot counts. Its left operand's columns are
    stat-major (PR 33), so the compiled row loop may hold no op that
    re-tiles it on the way to the dots: node-major, a ``reshape`` of its own
    rewrote it in every row chunk of every level (14.5 s of a 46.8 s search,
    and 2.38 GB of temporaries here at 1536). And the benchmark's histogram
    readers know the loop by its accumulators (``hist_op_pattern``): the
    program is held to that pattern here."""
    from cs230_distributed_machine_learning_tpu.ops import trees as ops_trees

    ds, n_binss = (_D_CONT, _D_COARSE), (n_bins, 4)
    assert ops_trees._resolve_hist_kernel(True, ds, n_binss, C) == "matmul"

    def lanes(local, xb_cont, xb_coarse, SC):
        return jax.vmap(lambda lo, sc: ops_trees._level_histogram_multi(
            lo, (xb_cont, xb_coarse), sc, n_nodes, n_binss, None, True))(local, SC)

    compiled = _lower_and_compile(
        lanes, _sds((S, _N_FOREST), jnp.int32), _sds((_N_FOREST, _D_CONT), jnp.int32),
        _sds((_N_FOREST, _D_COARSE), jnp.int32), _sds((S, _N_FOREST, C), jnp.float32))
    if compiled is None:
        return  # lowered only: no deviceless topology here
    rc, cols = ops_trees._HIST_ROW_CHUNK, n_nodes * C
    # an event's name in a device trace is its instruction without metadata
    text = [re.sub(r", metadata=\{[^}]*\}", "", line.strip()) for line in compiled.as_text().splitlines()
            if re.match(r"\s*(?:ROOT )?%\S+ = ", line)]
    operand = rf"s8\[{S},(?:{rc},{cols}|{cols},{rc})\]"
    retiled = [t[:160] for t in text if re.match(rf"(?:ROOT )?%\S+ = {operand}\S* (?:reshape|copy|transpose)\(", t)]
    assert not retiled, retiled
    assert any(re.search(rf"= {operand}", t) for t in text)  # the operand is there to be looked for
    work, cell = _perfbench_forest_cell()
    loops = [t for t in text if re.search(work.hist_op_pattern(cell), t)]
    assert len(loops) == 1 and loops[0].startswith("%while"), loops
    assert f"s32[{S},{cols},{_D_CONT * n_bins}]" in loops[0] and f"s32[{S},{cols},{_D_COARSE * 4}]" in loops[0]
    if n_nodes == 1536:
        assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# ---------------------------------------------------------------------------
# The boosted-trees cell (gbt_higgs.rs8, PR 34): the chunked step program of
# depth-8 GradientBoostingClassifier at 1 000 000 x 28, as one dispatch
# carries it: every trial's six folds, two stages a step.
# ---------------------------------------------------------------------------

_N_HIGGS, _D_HIGGS = 1_000_000, 28


def _perfbench_boost_cell():
    return _perfbench_cell("GradientBoostingClassifier", "gbt_higgs", "rs8")


def _boost_step(chunk):
    """(vstep, its example arguments, kernel, static) as
    ``trial_map._run_chunked`` builds them for ``chunk`` trials of the cell."""
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    _, cell = _perfbench_boost_cell()
    kernel = get_kernel("GradientBoostingClassifier")
    static_key, hyper = kernel.canonicalize(cell["config"]["estimator"]["params"])
    static = trial_map._resolved_static(kernel, static_key, _N_HIGGS, _D_HIGGS, 2)
    plan = kernel.chunked_plan(static, _N_HIGGS, _D_HIGGS, 2, S)
    X = {"X": _sds((_N_HIGGS, _D_HIGGS), jnp.float32), "xb": _sds((_N_HIGGS, _D_HIGGS), jnp.int32),
         "edges": _sds((_D_HIGGS, 127), jnp.float32)}

    def step_b(X, y, TW, EW, hyper, ci, state):
        return jax.vmap(lambda tw, st: kernel.chunk_step(
            X, y, tw, hyper, static, ci, st, plan))(TW, state)

    vstep = jax.vmap(step_b, in_axes=(None, None, None, None, 0, None, 0))
    args = (X, _sds((_N_HIGGS,), jnp.int32), _sds((S, _N_HIGGS), jnp.float32),
            _sds((S, _N_HIGGS), jnp.float32),
            {k: _sds((chunk,), jnp.float32) for k in sorted(hyper)}, _sds((), jnp.int32),
            _sds((chunk, S, _N_HIGGS, 2), jnp.float32))
    return vstep, args, kernel, static, plan


@pytest.mark.parametrize("chunk", [1, 8])
def test_boost_chunked_step_compiles_at_the_cell_shape(tpu_backend, chunk):
    """One trial's six folds, and the cell's dispatch of all eight trials'
    (48 lanes). Pinned: the float-stat histograms are eight row loops a
    stage (the root, then the left children of levels 1-7), each known to
    the benchmark's readers by its float32 accumulator (``hist_op_pattern``)
    and by nothing else of the program; the (g, h) operand enters the loops
    in bfloat16 and the bin one-hot is a predicate, never a float32 matrix
    (the configuration's ``precision``); a stage routes the table once, the
    builder's own walk (PR 38: the stage reads its update off the builder's
    leaf ids; until then ``predict_tree`` walked the finished tree again and
    the program held every routing fusion twice); the compiled program holds
    no [n, m] routing buffer, and the lane estimate the engine plans with is
    within 1.5 times of what a lane takes."""
    vstep, args, kernel, static, plan = _boost_step(chunk)
    assert plan["trees_per_chunk"] == 2 and static["_depth"] == 8 and static["_n_bins"] == 128
    compiled = _lower_and_compile(vstep, *args)
    if compiled is None:
        return  # lowered only: no deviceless topology here
    text = [re.sub(r", metadata=\{[^}]*\}", "", line.strip()) for line in compiled.as_text().splitlines()
            if re.match(r"\s*(?:ROOT )?%\S+ = ", line)]
    work, cell = _perfbench_boost_cell()
    loops = [t for t in text if re.search(work.hist_op_pattern(cell), t)]
    assert len(loops) == 8 and all(t.startswith("%while") for t in loops), [t[:80] for t in loops]
    nodes = sorted(int(re.search(rf"f32\[{chunk},{S},1,(\d+),{_D_HIGGS * 128}\]", t).group(1)) for t in loops)
    assert nodes == [2, 2, 4, 8, 16, 32, 64, 128]  # (g, h) x built nodes: root, then left children
    rows = -(-_N_HIGGS // 16384) * 16384
    assert all(f"bf16[{chunk},{S},1,{rows},2]" in t for t in loops[1:])  # rounded once, before the loop
    assert any(re.search(rf"= pred\[16384,{_D_HIGGS},128\]", t) for t in text)
    assert not any(re.search(rf"= f32\[16384,{_D_HIGGS * 128}\]", t) for t in text)
    # a level's go-left is one fusion, whose computation contracts the [n, 28]
    # bin codes with the level's [28, m] feature one-hot (``_col_select``): an
    # instruction sits in one computation and a fused computation has one
    # caller, so the contractions count the fusions
    shape = {m.group(1): m.group(2) for t in text if (m := re.match(r"(?:ROOT )?(%\S+) = (\S+)", t))}
    routed = []
    for t in text:
        dot = re.match(rf"(?:ROOT )?%\S+ = f32\[{_N_HIGGS},[\d,]+\]\S* convolution\(%\S+, (%[^\s)]+)\)", t)
        hot = dot and re.match(rf"pred\[(?:{chunk},)?{S},{_D_HIGGS}(?:,(\d+))?\]", shape.get(dot.group(1), ""))
        if hot:
            routed.append(int(hot.group(1) or 1))
    assert sorted(routed) == [1, 2, 4, 8, 16, 32, 64, 128], routed  # the root's compare, then m = 2 ... 128
    temp = compiled.memory_analysis().temp_size_in_bytes
    lanes = chunk * S
    assert temp < lanes * 75e6 + 0.2e9  # 2.03 GB a lane by the estimate before PR 34
    with pytest.MonkeyPatch.context() as mp:  # the estimate as a TPU process makes it
        mp.setattr("cs230_distributed_machine_learning_tpu.utils.backend.on_tpu", lambda: True)
        estimate = kernel.memory_estimate_mb(_N_HIGGS, _D_HIGGS, static) * 1e6
    assert estimate / 1.5 < temp / lanes < estimate * 1.5, (temp / lanes, estimate)


#: the streamed cell logreg_mnist8m.rs32 (PR 40): 1.6M rows of 784 pixels,
#: 10 classes, 32 trials x 6 splits, a v5e stage budget of 0.4 x 16.9 GB
_N_MNIST8M, _D_MNIST8M, _C_MNIST8M, _T_MNIST8M = 1_600_000, 784, 10, 32


def test_streamed_grad_block_fits_beside_the_cached_blocks(tpu_backend, monkeypatch):
    """The streamed LogReg gradient at the cell's block and lanes. Pinned:
    the block plan (lanes counted) gives six blocks; the compiled program
    keeps every [T, S, c, rows] intermediate rows-minor, so no class axis of
    10 is padded to 128 lanes (58 bytes a row a lane, against the plan's
    72), and its scratch and arguments beside the six cached blocks are
    under the chip."""
    from cs230_distributed_machine_learning_tpu.data import streaming
    from cs230_distributed_machine_learning_tpu.models import logistic

    monkeypatch.delenv("CS230_STREAM_BLOCK_ROWS", raising=False)
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", str(0.4 * 16.9e3))
    kernel = get_kernel("LogisticRegression")
    lane = kernel.stream_lane_row_bytes({"_n_classes": _C_MNIST8M})
    lanes = _T_MNIST8M * S
    plan = streaming.plan_blocks(_N_MNIST8M, _D_MNIST8M * 4, work_row_bytes=lanes * lane)
    assert plan.n_blocks == 6
    rows, dp = plan.rows, _D_MNIST8M + 1
    grad_block = logistic._stream_fns(rows, _D_MNIST8M, _C_MNIST8M, S, _T_MNIST8M, True, 1.0)[3].fn
    w4 = _sds((_T_MNIST8M, S, dp, _C_MNIST8M), jnp.float32)
    compiled = _lower_and_compile(
        grad_block, _sds((rows, _D_MNIST8M), jnp.float32), w4, w4, _sds((plan.n_pad,), jnp.int32),
        _sds((S, plan.n_pad), jnp.float32), _sds((), jnp.int32))
    if compiled is None:
        return  # lowered only: no deviceless topology here
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= rows * lanes * lane  # the plan's count is an upper bound
    assert mem.temp_size_in_bytes / (rows * lanes * _C_MNIST8M) < 6.5  # no 128-lane padding of c
    cached = plan.n_pad * _D_MNIST8M * 4
    assert cached + mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.5e9
