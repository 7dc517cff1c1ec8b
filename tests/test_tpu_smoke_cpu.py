"""chip_smoke.py's legs, end to end on the CPU at a tiny size.

The script itself runs on the chip only and holds no CPU fallback; this
file imports its functions and drives them with the Pallas kernels in
interpret mode, so the control flow (cold/warm jobs, the REST surface in a
thread, sklearn parity, clean shutdown) is debugged here and chip time is
spent on the chip's own questions.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: (d+1)*c > 512 selects the Nesterov method, and with it the packed fit,
#: at any n
DATASET = "synthetic_1500x128x7"


def test_flagship_and_rest_legs_run_the_packed_path(monkeypatch):
    from cs230_distributed_machine_learning_tpu.parallel import trial_map

    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    trial_map._compiled_cache.clear()
    obs = chip_smoke.flagship_leg(DATASET, 6, max_iter=30, cv=2)
    kinds = {k[0] for k in trial_map._compiled_cache if isinstance(k, tuple)}
    assert "batched" in kinds
    assert obs["fresh_executables_cold"] >= 1
    # the device metrics are null on the CPU by construction — the chip
    # run's check_device_work is what demands them
    assert obs["cost"]["mfu"] is None
    assert obs["cost"]["hbm_peak_bytes"] is None
    rows = chip_smoke.check_sklearn_parity(
        obs["data"], obs["results"], n_check=2, max_iter=30, cv=2, tol=0.1
    )
    assert len(rows) == 2

    # the mesh leg's comparison, on four of conftest's forced host devices
    import jax

    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    mesh = chip_smoke.flagship_leg(
        DATASET, 6, max_iter=30, cv=2, mesh=trial_mesh(jax.devices()[:4])
    )
    verdict = chip_smoke.check_mesh_results(mesh, obs)
    assert verdict["max_score_diff"] < chip_smoke.PARITY_TOL

    rest = chip_smoke.rest_leg(DATASET, 4, max_iter=30, cv=2)
    assert rest["healthz"]["device"]["platform"] == "cpu"
    assert rest["cost"]["n_groups"] >= 1


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.startswith("device: platform=cpu")
    assert '"ok"' not in r.stdout
