"""The cells' executables, compiled for a described v5e with no chip
attached: a later PR that breaks a cell's shape learns it here, at no chip
time. A compile that passes is not a chip run. The topology is described
inside a fixture, never at import (one process may hold libtpu at a time).
"""

import importlib.util
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run():
    if "perfbench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location("perfbench_run", os.path.join(BENCH, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["perfbench_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["perfbench_run"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """The package's ``auto`` valves decide as on a TPU backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for valve in ("CS230_PALLAS_INTERPRET", "CS230_FUSED_STEP", "CS230_MASKED_GRAD"):
        monkeypatch.delenv(valve, raising=False)
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _compile_cell(workload, one_chip, chunk, hyper, extra=None):
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel

    cell = _run().load_cell(workload)
    ds, est = cell["config"]["dataset"], cell["config"]["estimator"]
    n, d, c = ds["n_samples"], ds["n_features"], ds["n_classes"]
    S = cell["traffic"]["cv"] + 1
    kernel = get_kernel(est["class"])
    params = {k: (tuple(v) if isinstance(v, list) else v) for k, v in est["params"].items()}
    static_key, _ = kernel.canonicalize(params)
    static = kernel.resolve_static(kernel.static_from_key(static_key), n, d, c)
    static["_n_classes"] = c
    if hasattr(kernel, "bucket_static"):
        static = kernel.bucket_static(static, [params])
    assert kernel.batched_applicable(static, n, d)
    assert chunk <= kernel.batched_chunk_cap and chunk % kernel.batched_trial_multiple == 0
    fn = kernel.build_batched_fn(static, n, d, c, S, chunk)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)
    hypers = {h: sds((chunk,), jnp.float32) for h in hyper}
    hypers.update({k: sds(*v) for k, v in (extra or {}).items()})
    compiled = jax.jit(fn).lower(
        sds((n, d), jnp.float32), sds((n,), jnp.int32),
        sds((S, n), jnp.float32), sds((S, n), jnp.float32), hypers).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is on the path
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.5e9
    return mem


def test_logreg_rows5m_packed_fit_compiles(one_chip, tpu_backend):
    """n=5M, one 128-trial weight block, 100 steps, the staged bf16 design
    matrix and Lipschitz bound handed in as the trial engine does."""
    n_pad, dpp = -(-5_000_000 // 2048) * 2048, 64
    mem = _compile_cell("logreg_rows5m.rs128", one_chip, 128, ("C", "max_iter", "tol"),
                        {"_logreg_ab": ((n_pad, dpp), jnp.bfloat16),
                         "_logreg_lam_max": ((6,), jnp.float32)})
    assert mem.temp_size_in_bytes > 1e9  # a cell that leaves the chip empty is refused


def test_mlp_mnist_fused_epochs_compile(one_chip, tpu_backend):
    """60 000 x 784, 784-512-512-10, batch 200, 5 epochs, one 64-trial chunk."""
    mem = _compile_cell("mlp_mnist.rs64", one_chip, 64, ("alpha", "learning_rate_init"))
    assert mem.temp_size_in_bytes > 1e9
