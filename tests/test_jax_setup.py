"""Process-level JAX setup (utils/jax_setup.py) and the platform module
(utils/backend.py): platform pinning, the compile-cache placement rule, and
the interpret decision. Fresh subprocesses where setup_jax latches.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = (
    "from cs230_distributed_machine_learning_tpu.utils.jax_setup import setup_jax\n"
    "setup_jax()\n"
    "import jax\n"
    "print('CACHEDIR=' + str(jax.config.jax_compilation_cache_dir))\n"
    "print('MINSECS=' + str(jax.config.jax_persistent_cache_min_compile_time_secs))\n"
)


def _run(script, extra_env=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-c", script], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=180,
    )


def _field(stdout, name):
    return stdout.split(name + "=")[1].splitlines()[0].strip()


@pytest.mark.slow  # spawns a fresh interpreter importing jax (~10 s)
def test_tpuml_platform_pins_backend():
    r = _run(
        "from cs230_distributed_machine_learning_tpu.utils.jax_setup import setup_jax\n"
        "setup_jax()\n"
        "import jax\n"
        "print('BACKEND=' + jax.default_backend())\n",
        {"TPUML_PLATFORM": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-500:]
    assert "BACKEND=cpu" in r.stdout, r.stdout


def test_cache_dir_is_the_fixed_in_checkout_path():
    """JAX_COMPILATION_CACHE_DIR unset: one fixed directory inside the
    checkout — never a function of XLA_FLAGS, the platform pin or anything
    else that moves (the path is part of every cache key)."""
    want = os.path.join(REPO, ".jax_cache")
    seen = set()
    for env in (
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
         "TPUML_PLATFORM": "cpu"},
    ):
        r = _run(_PRINT_CACHE_DIR, env, drop=("JAX_COMPILATION_CACHE_DIR",))
        assert r.returncode == 0, r.stderr[-500:]
        seen.add(_field(r.stdout, "CACHEDIR"))
        assert _field(r.stdout, "MINSECS") == "0.0"
    assert seen == {want}, seen


def test_env_cache_dir_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the code sets no directory at all —
    JAX's own reading of the variable is what the config shows."""
    placed = str(tmp_path / "placed_from_outside")
    r = _run(_PRINT_CACHE_DIR, {"JAX_COMPILATION_CACHE_DIR": placed})
    assert r.returncode == 0, r.stderr[-500:]
    assert _field(r.stdout, "CACHEDIR") == placed
    assert not os.path.exists(placed)  # nobody here created or touched it
    assert _field(r.stdout, "MINSECS") == "0.0"

    import inspect

    from cs230_distributed_machine_learning_tpu.utils import jax_setup

    src = inspect.getsource(jax_setup.setup_jax)
    assert src.count('"jax_compilation_cache_dir"') == 1  # the unset branch


def test_host_fingerprint_is_stable():
    """CPU-lowered AOT exports are partitioned by host capability set
    (utils/aot_cache._generation); the fingerprint must be deterministic."""
    from cs230_distributed_machine_learning_tpu.utils.jax_setup import (
        host_fingerprint,
    )

    fp = host_fingerprint()
    assert fp and len(fp) == 16
    assert host_fingerprint() == fp


def test_interpret_only_on_the_cpu_backend(monkeypatch):
    """The platform module owns ``interpret=``: the interpreter is for
    CS230_PALLAS_INTERPRET=1 on the CPU backend and nothing else — on any
    other backend, named "tpu" or not, a Pallas kernel compiles."""
    import jax

    from cs230_distributed_machine_learning_tpu.utils import backend

    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    assert backend.pallas_interpret()  # conftest pins the CPU backend
    for name in ("tpu", "gpu", "some-new-accelerator"):
        monkeypatch.setattr(jax, "default_backend", lambda n=name: n)
        assert not backend.pallas_interpret(), name
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.delenv("CS230_PALLAS_INTERPRET")
    assert not backend.pallas_interpret()


def test_auto_never_selects_pallas_inside_a_mesh_trace(monkeypatch):
    import jax

    from cs230_distributed_machine_learning_tpu.utils import backend

    assert not backend.auto_pallas()  # CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.auto_pallas()
    with backend.xla_formulations():
        assert not backend.auto_pallas()
        with backend.xla_formulations():
            assert not backend.auto_pallas()
        assert not backend.auto_pallas()
    assert backend.auto_pallas()


def test_unknown_accelerator_is_an_error_not_a_default(monkeypatch):
    """No peak rate and no memory size is assumed for a device the
    platform module does not know; on the CPU both have defined answers."""
    import jax

    from cs230_distributed_machine_learning_tpu.utils import backend

    assert backend.device_peak_flops() is None
    assert backend.hbm_peak_bytes() is None
    assert backend.device_memory_mb() > 0

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v99 prototype"

        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDevice()])
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [FakeDevice()])
    with pytest.raises(RuntimeError, match="no peak FLOP/s known"):
        backend.device_peak_flops()
    with pytest.raises(RuntimeError, match="bytes_limit"):
        backend.device_memory_mb()
    FakeDevice.device_kind = "TPU v5 lite"
    assert backend.device_peak_flops() == 197e12


@pytest.mark.slow  # spawns a fresh interpreter importing jax (~10 s)
def test_aot_cache_disabled_on_cpu_backend():
    r = _run(
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from cs230_distributed_machine_learning_tpu.utils import aot_cache\n"
        "print('ENABLED=' + str(aot_cache.enabled()))\n",
    )
    assert r.returncode == 0, r.stderr[-500:]
    assert "ENABLED=False" in r.stdout, r.stdout
