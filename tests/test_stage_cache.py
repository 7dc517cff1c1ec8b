"""Multi-tenant staged-dataset cache (data/stage_cache.py): single-flight
uploads, content-fingerprint keying, refcounted LRU eviction under a
device-memory budget, the CS230_STAGE_CACHE=0 parity valve, and the
upload-counter contract the concurrency benchmark (benchmarks/staging_concurrency.py) relies on."""

import threading
import time

import numpy as np
import pytest

from cs230_distributed_machine_learning_tpu.data import stage_cache as sc
from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel import trial_map as tm


@pytest.fixture(autouse=True)
def _fresh_cache():
    sc.STAGE_CACHE.clear()
    yield
    sc.STAGE_CACHE.clear()


def _data(n=200, d=6, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(dtype)
    y = (X[:, 0] > 0).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=2)


# ---------------- single-flight / upload counter ----------------


def test_single_flight_one_upload_under_concurrency():
    """8 concurrent misses on one key perform exactly ONE make() — the
    O(1)-uploads-per-(dataset, device) contract of the concurrency
    benchmark, pinned fast here."""
    made = []
    barrier = threading.Barrier(8)

    def make():
        made.append(1)
        time.sleep(0.05)  # wide window: every thread arrives mid-flight
        return np.zeros(16, np.float32)

    outcomes = []

    def job():
        barrier.wait()
        _, outcome = sc.STAGE_CACHE.get_or_stage(("fp", "dev", "X"), make)
        outcomes.append(outcome)

    threads = [threading.Thread(target=job) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(made) == 1
    assert sc.STAGE_CACHE.stats()["uploads"] == 1
    assert outcomes.count("miss") == 1
    assert set(outcomes) <= {"miss", "wait"}
    assert sc.STAGE_CACHE.uploads_by_key()[("fp", "dev", "X")] == 1


def test_failed_make_releases_waiters_to_retry():
    order = []

    def bad_then_good():
        order.append("call")
        if len(order) == 1:
            raise RuntimeError("staging failed")
        return np.zeros(4)

    with pytest.raises(RuntimeError):
        sc.STAGE_CACHE.get_or_stage(("k",), bad_then_good)
    val, outcome = sc.STAGE_CACHE.get_or_stage(("k",), bad_then_good)
    assert outcome == "miss" and val is not None


# ---------------- fingerprint collision safety ----------------


def test_fingerprint_same_content_same_key():
    a, b = _data(seed=3), _data(seed=3)
    assert a is not b
    assert sc.dataset_fingerprint(a) == sc.dataset_fingerprint(b)


def test_fingerprint_dtype_differs():
    """Same values, different dtype: bf16/f32 stagings must never collide
    (widened bytes would silently serve the wrong precision)."""
    a = _data(seed=1, dtype=np.float32)
    b = _data(seed=1, dtype=np.float64)
    assert np.allclose(a.X, b.X)
    assert sc.dataset_fingerprint(a) != sc.dataset_fingerprint(b)


def test_fingerprint_preprocess_salt_differs():
    a, b = _data(seed=2), _data(seed=2)
    object.__setattr__(b, "preprocess_salt", "scaler-v2")
    assert sc.dataset_fingerprint(a) != sc.dataset_fingerprint(b)


def test_fingerprint_content_differs():
    assert sc.dataset_fingerprint(_data(seed=4)) != sc.dataset_fingerprint(
        _data(seed=5)
    )


# ---------------- refcounting + LRU eviction under pressure ----------------


def test_lru_eviction_under_memory_budget(monkeypatch):
    """Budget fits ~2 of 3 equal entries: the LRU one goes, the recently
    used stays, and re-touching refreshes recency."""
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "0.01")  # 10 kB
    mk = lambda: np.zeros(1000, np.float32)  # 4 kB each  # noqa: E731
    sc.STAGE_CACHE.get_or_stage(("a",), mk)
    sc.STAGE_CACHE.get_or_stage(("b",), mk)
    sc.STAGE_CACHE.get_or_stage(("a",), mk)  # refresh a
    sc.STAGE_CACHE.get_or_stage(("c",), mk)  # over budget -> evict b (LRU)
    assert sc.STAGE_CACHE.contains(("a",))
    assert not sc.STAGE_CACHE.contains(("b",))
    assert sc.STAGE_CACHE.contains(("c",))
    assert sc.STAGE_CACHE.stats()["evictions"] == 1


def test_pinned_entries_survive_memory_pressure(monkeypatch):
    """A pinned (in-flight run) entry is never evicted, even as LRU; the
    budget overflow is recorded instead. After the pin scope closes it
    becomes evictable again."""
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "0.008")  # 8 kB
    mk = lambda: np.zeros(1000, np.float32)  # noqa: E731
    token = sc.STAGE_CACHE.pin_begin()
    sc.STAGE_CACHE.get_or_stage(("pinned",), mk)  # pinned by the scope
    sc.STAGE_CACHE.get_or_stage(("lru",), mk)
    assert sc.STAGE_CACHE.stats()["pinned"] >= 1
    sc.STAGE_CACHE.get_or_stage(("new1",), mk)
    sc.STAGE_CACHE.get_or_stage(("new2",), mk)
    assert sc.STAGE_CACHE.contains(("pinned",))  # LRU yet untouchable
    sc.STAGE_CACHE.pin_end(token)
    assert sc.STAGE_CACHE.stats()["pinned"] == 0
    sc.STAGE_CACHE.get_or_stage(("new3",), mk)
    assert not sc.STAGE_CACHE.contains(("pinned",))  # now evictable


# ---------------- trial-engine integration ----------------


def _run(data, params=None, n_folds=2):
    kernel = get_kernel("GaussianNB")
    y = np.asarray(data.y)
    plan = build_split_plan(
        y, task="classification", n_folds=n_folds, test_size=0.2,
        random_state=42,
    )
    return tm.run_trials(kernel, data, plan, [params or {}])


def test_concurrent_tenants_stage_once():
    """The tentpole contract end to end: 8 concurrent jobs, each with its
    OWN TrialData over the same dataset content, stage exactly once per
    (dataset, device, staged form) — one X upload + one fold-tensor
    upload, upload counter pinned."""
    datasets = [_data(seed=7) for _ in range(8)]
    barrier = threading.Barrier(8)
    errors = []

    def job(d):
        try:
            barrier.wait()
            run = _run(d)
            assert run.trial_metrics
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=job, args=(d,)) for d in datasets]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    stats = sc.STAGE_CACHE.stats()
    assert stats["uploads"] == 2, stats  # X once, fold tensors once
    assert max(sc.STAGE_CACHE.uploads_by_key().values()) == 1


def test_stage_cache_parity_valve(monkeypatch):
    """CS230_STAGE_CACHE=0 restores the legacy per-TrialData staging path
    with identical results (the bit-for-bit valve of the acceptance
    criteria)."""
    on = _run(_data(seed=9), {"var_smoothing": 1e-9})
    uploads_after_on = sc.STAGE_CACHE.stats()["uploads"]
    monkeypatch.setenv("CS230_STAGE_CACHE", "0")
    off = _run(_data(seed=9), {"var_smoothing": 1e-9})
    assert on.trial_metrics == off.trial_metrics
    # and the valve really bypassed the global cache: no new uploads,
    # the legacy path staged onto the TrialData object instead
    assert sc.STAGE_CACHE.stats()["uploads"] == uploads_after_on


def test_run_pins_entries_only_while_running():
    _run(_data(seed=11))
    assert sc.STAGE_CACHE.stats()["entries"] >= 1
    assert sc.STAGE_CACHE.stats()["pinned"] == 0  # scope closed with the run


@pytest.mark.parametrize("n_trials,staged", [(2, 2), (65, 3)])
def test_logreg_packed_precomputes_staged_once(monkeypatch, n_trials, staged):
    """The packed LogReg path's dispatch-invariant precomputes (the
    per-split Lipschitz power iteration and the padded bf16 design
    matrix, ISSUE 10 satellites; at a block of 128 trials also the step
    kernel's occupancy table, which a narrower block never reads and so
    never stages) are staged-form cache entries: the second run over the
    same (dataset, folds) pair is a pure cache hit — exactly ONE upload
    per precompute key, ever."""
    monkeypatch.setenv("CS230_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(3)
    X = rng.randn(600, 7).astype(np.float32)
    y = rng.randint(0, 3, 600).astype(np.int32)
    data = TrialData(X=X, y=y, n_classes=3)
    plan = build_split_plan(data.y, task="classification", n_folds=3)
    kernel = get_kernel("LogisticRegression")
    orig_resolve = kernel.resolve_static
    monkeypatch.setattr(
        kernel,
        "resolve_static",
        lambda s, n, d, c: {**orig_resolve(s, n, d, c), "_method": "nesterov"},
    )
    params = [{"C": c, "max_iter": 15} for c in np.geomspace(0.1, 1.0, n_trials)]

    def extra_uploads():
        # this dataset's entries only: a job another test left running in
        # the process (a prewarm, an agent) may stage its own extras here
        fp = sc.dataset_fingerprint(data)
        return {
            k: v
            for k, v in sc.STAGE_CACHE.uploads_by_key().items()
            if "batched_extra" in str(k) and k[0] == fp
        }

    first = tm.run_trials(kernel, data, plan, params)
    ups = extra_uploads()
    assert len(ups) == staged, ups  # lam_max + padded bf16 Ab (+ the table)
    assert all(
        "lam_max" in str(k) or "'ab'" in str(k) or "'occ'" in str(k) for k in ups
    )
    assert all(v == 1 for v in ups.values()), ups
    hits_before = sc.STAGE_CACHE.stats()["hits"]

    second = tm.run_trials(kernel, data, plan, params)
    ups2 = extra_uploads()
    assert ups2 == ups, "second dispatch re-uploaded a precompute"
    assert sc.STAGE_CACHE.stats()["hits"] >= hits_before + staged
    for a, b in zip(first.trial_metrics, second.trial_metrics):
        assert a["mean_cv_score"] == pytest.approx(b["mean_cv_score"])


# ---------------- metrics catalog ----------------


def test_stage_cache_metrics_in_prom_catalog():
    """The cache/prewarm families are registered eagerly and visible in
    the Prometheus exposition (docs parity is enforced separately by
    test_flight_recorder's catalog gate)."""
    from cs230_distributed_machine_learning_tpu.obs import (
        REGISTRY,
        render_prometheus,
    )

    names = REGISTRY.names()
    for name in (
        "tpuml_stage_cache_hits_total",
        "tpuml_stage_cache_misses_total",
        "tpuml_stage_cache_uploads_total",
        "tpuml_stage_cache_evictions_total",
        "tpuml_stage_cache_bytes",
        "tpuml_stage_cache_entries",
        "tpuml_prewarm_warmed_total",
        "tpuml_prewarm_skipped_total",
    ):
        assert name in names
        assert name in render_prometheus()


# ---------------- mesh-shaped entries (elastic trial fabric) ----------------


def _mesh_job(data, mesh, n_trials=16):
    import numpy as np

    kernel = get_kernel("LogisticRegression")
    plan = build_split_plan(
        np.asarray(data.y), task="classification", n_folds=2,
        test_size=0.2, random_state=0,
    )
    params = [{"C": 10.0 ** (i / 4.0 - 2.0)} for i in range(n_trials)]
    return tm.run_trials(kernel, data, plan, params, mesh=mesh)


def _x_upload_count():
    return sum(
        n for key, n in sc.STAGE_CACHE.uploads_by_key().items()
        if "X" in key
    )


def test_mesh_staging_one_host_upload_per_dataset_host():
    """The mesh contract: with N devices, the dataset crosses the slow
    host link ONCE per (dataset, host) — the mesh-placed form is built by
    on-device replication (counted separately), and a second tenant over
    identical content adds no transfer at all."""
    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    import jax

    assert len(jax.devices()) >= 8  # conftest forces 8 host devices
    data = _data(n=256, d=8, seed=3)
    res = _mesh_job(data, trial_mesh())
    assert len(res.trial_metrics) == 16
    stats = sc.STAGE_CACHE.stats()
    assert _x_upload_count() == 1  # <=1 host upload for X, N devices
    assert stats["replications"] >= 1
    assert stats["host_upload_bytes"] > 0
    assert stats["ici_bytes"] > 0
    uploads_before = stats["uploads"]

    # second tenant, fresh TrialData, same content: pure cache hits
    data2 = _data(n=256, d=8, seed=3)
    _mesh_job(data2, trial_mesh())
    stats2 = sc.STAGE_CACHE.stats()
    assert stats2["uploads"] == uploads_before
    assert stats2["replications"] == stats["replications"]


def test_mesh_forms_coexist_and_match_per_device_staging():
    """1-D trial-replicated and 2-D data-sharded staged forms of one
    dataset coexist under mesh-axis subkeys, and every form's scores are
    identical to the legacy per-device staging path (cache valve off)."""
    import os

    from cs230_distributed_machine_learning_tpu.parallel.mesh import trial_mesh

    data = _data(n=256, d=8, seed=4)
    r1 = _mesh_job(data, trial_mesh())
    r2 = _mesh_job(data, trial_mesh(data_parallel=2))
    mesh_keys = [k for k in sc.STAGE_CACHE.keys() if "mesh" in k]
    forms = {k[-1] for k in mesh_keys if "X" in k}
    assert {"repl", "rows"} <= forms
    # legacy parity: identical scores without the cache (jit-placed)
    os.environ["CS230_STAGE_CACHE"] = "0"
    try:
        legacy1 = _mesh_job(data, trial_mesh())
        legacy2 = _mesh_job(data, trial_mesh(data_parallel=2))
    finally:
        os.environ.pop("CS230_STAGE_CACHE")
    key = "mean_cv_score"
    assert [m[key] for m in r1.trial_metrics] == [
        m[key] for m in legacy1.trial_metrics
    ]
    assert [m[key] for m in r2.trial_metrics] == [
        m[key] for m in legacy2.trial_metrics
    ]


def test_mesh_single_flight_under_8_thread_miss():
    """8 concurrent mesh stagings of one dataset perform ONE host-upload make
    and ONE replicate make — single-flight holds through the two-layer
    (host entry -> mesh entry) nesting."""
    import numpy as np

    host_makes, mesh_makes = [], []
    barrier = threading.Barrier(8)

    def stage_mesh():
        def make_host():
            host_makes.append(1)
            time.sleep(0.05)
            return np.zeros(1024, np.float32)

        def make_mesh():
            host, _ = sc.STAGE_CACHE.get_or_stage(
                ("fp", "host", "X", "dev"), make_host
            )
            mesh_makes.append(1)
            time.sleep(0.02)
            return host + 0  # the "replicated" form

        return sc.STAGE_CACHE.get_or_stage(
            ("fp", "host", "X", "mesh", (("trials", 8),), "repl"),
            make_mesh, transport="ici", ici_bytes=7 * 4096,
        )

    results = []

    def job():
        barrier.wait()
        results.append(stage_mesh())

    threads = [threading.Thread(target=job) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(host_makes) == 1
    assert len(mesh_makes) == 1
    stats = sc.STAGE_CACHE.stats()
    assert stats["uploads"] == 1  # the host-upload layer
    assert stats["replications"] == 1  # the ICI layer
    assert stats["ici_bytes"] == 7 * 4096
    assert [r[1] for r in results].count("miss") == 1


def test_mesh_metrics_in_prom_catalog():
    from cs230_distributed_machine_learning_tpu.obs import (
        REGISTRY,
        render_prometheus,
    )

    names = REGISTRY.names()
    for name in (
        "tpuml_stage_cache_replications_total",
        "tpuml_stage_cache_host_upload_bytes_total",
        "tpuml_stage_cache_ici_bytes_total",
        "tpuml_mesh_generation",
        "tpuml_mesh_devices_total",
        "tpuml_mesh_reshards_total",
    ):
        assert name in names
        assert name in render_prometheus()
