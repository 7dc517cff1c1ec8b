"""Test harness: virtual 8-device CPU mesh.

Mirrors how the reference fakes its AWS fleet with docker-compose
(SURVEY.md §4): trial-parallel/collective logic runs on 8 XLA host devices
so scheduler and sharding behavior is exercised without TPU hardware.
Must run before jax initializes a backend, hence the env mutation at import.
"""

import os

# TPUML_TEST_PLATFORM=tpu lets the gated slow-parity tests (deep-arena
# Covertype fits) run on the real chip — they are compute-infeasible on
# the CPU backend. Everything else stays pinned to the virtual CPU mesh.
_plat = os.environ.get("TPUML_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _plat
_flags = os.environ.get("XLA_FLAGS", "")
if _plat == "cpu" and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest


@pytest.fixture(autouse=True)
def _tmp_storage(tmp_path, monkeypatch):
    """Point the framework's storage root at a per-test tmpdir."""
    from cs230_distributed_machine_learning_tpu.utils.config import (
        FrameworkConfig,
        set_config,
    )

    cfg = FrameworkConfig.load(env={})
    cfg.storage.root = str(tmp_path / "tpuml")
    set_config(cfg)
    yield
    set_config(FrameworkConfig.load(env={}))


@pytest.fixture(scope="session")
def eight_device_mesh():
    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    return trial_mesh()


def pytest_sessionfinish(session, exitstatus):
    """CI forensics (deploy/ci.sh): on a red run, snapshot this process's
    metrics registry in Prometheus text format AND the flight recorder's
    event ring as JSONL, so the failed suite's counters/histograms and
    scheduling decisions ride the workflow artifact next to the span/event
    journals (which CS230_JOURNAL_DIR already collects file-side)."""
    if exitstatus == 0:
        return
    path = os.environ.get("CS230_METRICS_SNAPSHOT")
    if path:
        try:
            from cs230_distributed_machine_learning_tpu.obs import (
                render_prometheus,
            )

            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with open(path, "w") as f:
                f.write(render_prometheus())
        except Exception:  # noqa: BLE001 — forensics must not mask the failure
            pass
    path = os.environ.get("CS230_EVENTS_SNAPSHOT")
    if path:
        try:
            import json

            from cs230_distributed_machine_learning_tpu.obs import RECORDER

            events, _ = RECORDER.events(since=0, limit=10 ** 9)
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with open(path, "w") as f:
                for e in events:
                    f.write(json.dumps(e, default=str) + "\n")
        except Exception:  # noqa: BLE001 — forensics must not mask the failure
            pass
