"""Out-of-core row-block streaming (data/streaming.py + the engine's
``_run_streamed`` path): block plans, double-buffered staged uploads,
streamed-vs-single-shot score parity (bitwise for integer tree stats),
prefetch pinning under LRU pressure, per-host disjoint block sets, the
CS230_STREAM valve, the CS230_STAGE_STRICT budget wall the streamer
exists to remove, and chunked CSV ingest."""

import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cs230_distributed_machine_learning_tpu.data import stage_cache as sc
from cs230_distributed_machine_learning_tpu.data import streaming as st
from cs230_distributed_machine_learning_tpu.models.base import TrialData
from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
from cs230_distributed_machine_learning_tpu.obs import REGISTRY
from cs230_distributed_machine_learning_tpu.obs.recorder import RECORDER
from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
from cs230_distributed_machine_learning_tpu.parallel.trial_map import run_trials


@pytest.fixture(autouse=True)
def _fresh_cache(monkeypatch):
    monkeypatch.setenv("CS230_STAGE_CACHE", "1")
    sc.STAGE_CACHE.clear()
    yield
    sc.STAGE_CACHE.clear()


def _logreg_data(n=1500, d=128, c=7, seed=7):
    """d is sized so resolve_static picks NESTEROV ((d+1)*c > 512) — the
    only LogReg method with a streamed driver — at a CPU-friendly n."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    W = rng.normal(size=(d, c))
    y = np.argmax(X @ W + rng.normal(scale=0.5, size=(n, c)), 1).astype(np.int32)
    return TrialData(X=X, y=y, n_classes=c)


def _scores(out):
    return [
        (m["accuracy"], tuple(m.get("cv_scores", ()))) for m in out.trial_metrics
    ]


# ---------------- block plans / the valve ----------------


def test_plan_blocks_covers_and_pads():
    plan = st.plan_blocks(1000, row_bytes=4, rows=256)
    assert (plan.n_blocks, plan.rows, plan.n_pad) == (4, 256, 1024)
    assert [plan.size(i) for i in plan.block_ids()] == [256, 256, 256, 232]
    assert sum(plan.size(i) for i in plan.block_ids()) == 1000


def test_plan_blocks_env_override(monkeypatch):
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "100")
    plan = st.plan_blocks(350, row_bytes=4)
    assert plan.rows == 100 and plan.n_blocks == 4


def test_stream_mode_resolution(monkeypatch):
    for raw, want in [("0", "off"), ("off", "off"), ("1", "force"),
                      ("force", "force"), ("auto", "auto"), ("junk", "auto")]:
        monkeypatch.setenv("CS230_STREAM", raw)
        assert st.stream_mode() == want
    monkeypatch.delenv("CS230_STREAM")
    assert st.stream_mode() == "auto"


def test_should_stream_auto_threshold(monkeypatch):
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "1")  # budget = 1e6 bytes
    monkeypatch.setenv("CS230_STREAM", "auto")
    assert not st.should_stream(400_000)   # under half the budget
    assert st.should_stream(600_000)       # over half
    monkeypatch.setenv("CS230_STREAM", "off")
    assert not st.should_stream(10**12)
    monkeypatch.setenv("CS230_STREAM", "force")
    assert st.should_stream(1)


def test_host_block_set_partitions_disjointly():
    for n_blocks, n_shards in [(10, 3), (8, 8), (3, 5), (64, 4)]:
        seen = []
        for s in range(n_shards):
            seen.extend(st.host_block_set(n_blocks, n_shards, s))
        assert sorted(seen) == list(range(n_blocks))  # disjoint + complete
        sizes = [len(st.host_block_set(n_blocks, n_shards, s))
                 for s in range(n_shards)]
        assert max(sizes) - min(sizes) <= 1


# ---------------- engine parity: LogReg (float accumulation) ----------------


def test_logreg_streamed_engine_parity(monkeypatch):
    """CS230_STREAM=force matches the legacy single-shot engine path on an
    n that is NOT a multiple of the block height (pad rows carry zero
    weight). Float gradient block sums reorder f32 additions, so parity
    is to tolerance — the integer-stat tree test below is the bitwise one."""
    data = _logreg_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=2)
    kern = get_kernel("LogisticRegression")
    static = kern.resolve_static(
        kern.static_from_key(kern.canonicalize({"C": 1.0})[0]),
        data.X.shape[0], data.X.shape[1], data.n_classes)
    assert static["_method"] == "nesterov"
    params = [{"C": 1.0, "max_iter": 20}, {"C": 0.1, "max_iter": 20}]

    monkeypatch.setenv("CS230_STREAM", "0")
    legacy = run_trials(kern, data, plan, params)
    monkeypatch.setenv("CS230_STREAM", "force")
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "512")
    streamed = run_trials(kern, data, plan, params)

    assert 1500 % 512 != 0
    for (a0, cv0), (a1, cv1) in zip(_scores(legacy), _scores(streamed)):
        assert abs(a0 - a1) < 2e-3
        assert np.allclose(cv0, cv1, atol=2e-3)
    # the streamed bucket dispatched per block, not once
    assert streamed.n_dispatches > legacy.n_dispatches
    block_keys = [k for k in sc.STAGE_CACHE.uploads_by_key() if "block" in k]
    assert len(block_keys) == 3  # ceil(1500 / 512)


def test_stream_off_is_legacy_bit_for_bit(monkeypatch):
    """CS230_STREAM=0 must take the exact legacy staging path: identical
    metrics to an untouched run on small data (auto resolves to
    single-shot there too) and NO block entries in the stage cache."""
    data = _logreg_data(n=400, d=128)
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=2)
    kern = get_kernel("LogisticRegression")
    params = [{"C": 1.0, "max_iter": 15}]

    monkeypatch.delenv("CS230_STREAM", raising=False)
    auto = run_trials(kern, data, plan, params)
    sc.STAGE_CACHE.clear()
    monkeypatch.setenv("CS230_STREAM", "0")
    off = run_trials(kern, data, plan, params)
    assert _scores(auto) == _scores(off)
    assert not [k for k in sc.STAGE_CACHE.uploads_by_key() if "block" in k]


# ---------------- engine parity: RF (bitwise integer stats) ----------------


def test_rf_streamed_engine_parity_bitwise(monkeypatch):
    """Streamed forest scores are BITWISE equal to the legacy path: the
    histogram accumulation routes through the order-free integer-stats
    form, so per-tree splits and leaf values are identical."""
    data = _logreg_data(n=700, d=12, c=3)
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=2)
    kern = get_kernel("RandomForestClassifier")
    params = [{"n_estimators": 2, "max_depth": 3, "n_bins": 16,
               "max_features": 4, "random_state": 3}]

    monkeypatch.setenv("CS230_STREAM", "0")
    legacy = run_trials(kern, data, plan, params)
    monkeypatch.setenv("CS230_STREAM", "force")
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "256")
    streamed = run_trials(kern, data, plan, params)
    assert _scores(legacy) == _scores(streamed)


def test_build_tree_streamed_bitwise_vs_build_tree():
    """The block-accumulated level builder reproduces build_tree's splits,
    leaf values, and final node ids EXACTLY (same PRNG stream, same
    integer histogram stats, same subtraction trick)."""
    from cs230_distributed_machine_learning_tpu.ops.trees import (
        build_tree, build_tree_streamed,
    )

    rng = np.random.default_rng(5)
    n, d, c, n_bins, depth = 700, 9, 3, 16, 3
    xb = rng.integers(0, n_bins, size=(n, d)).astype(np.int32)
    y = rng.integers(0, c, size=(n,))
    w = rng.integers(0, 3, size=(n,)).astype(np.float32)
    S = jax.nn.one_hot(jnp.asarray(y), c, dtype=jnp.float32) * w[:, None]
    C = jnp.asarray(w)
    key = jax.random.PRNGKey(11)

    ref = build_tree(
        jnp.asarray(xb), S, C, depth=depth, n_bins=n_bins, max_features=4,
        key=key, precision=jax.lax.Precision.DEFAULT, count_from_stats=True,
    )

    plan = st.plan_blocks(n, row_bytes=d * 4, rows=256)
    pad = plan.n_pad - n
    xb_pad = np.concatenate([xb, np.zeros((pad, d), np.int32)])
    S_pad = jnp.concatenate([S, jnp.zeros((pad, c))])
    C_pad = jnp.concatenate([C, jnp.zeros((pad,))])

    def stream_pass(fn, carry, *consts):
        for i in plan.block_ids():
            s = plan.start(i)
            blk = jnp.asarray(xb_pad[s : s + plan.rows])
            carry = fn(carry, *consts, blk, jnp.asarray(s, jnp.int32))
        return carry

    tree, node = build_tree_streamed(
        stream_pass, S_pad, C_pad, d, depth=depth, n_bins=n_bins,
        max_features=4, key=key,
        precision=jax.lax.Precision.DEFAULT, count_from_stats=True,
    )
    for k in ("split_feat", "split_bin", "leaf_val", "leaf_weight"):
        assert np.array_equal(np.asarray(tree[k]), np.asarray(ref[k])), k


# ---------------- the OOM repro the tentpole removes ----------------


def _strict_small_budget(monkeypatch):
    monkeypatch.setenv("CS230_STAGE_STRICT", "1")
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "0.3")  # 300 KB wall
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "256")  # 128 KB blocks


def test_oom_repro_logreg_strict_budget(monkeypatch):
    """THE acceptance pin: a dataset over the stage budget hard-fails the
    legacy single-shot path (CS230_STAGE_STRICT budget wall — the test
    double for a device OOM) and COMPLETES under CS230_STREAM=auto, whose
    block working set stays inside the budget. X is 1500x128 f32 =
    768 KB against a 300 KB budget."""
    _strict_small_budget(monkeypatch)
    data = _logreg_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=0)
    kern = get_kernel("LogisticRegression")
    params = [{"C": 1.0, "max_iter": 10}]

    monkeypatch.setenv("CS230_STREAM", "0")
    with pytest.raises(sc.StageBudgetExceeded):
        run_trials(kern, data, plan, params)

    sc.STAGE_CACHE.clear()
    monkeypatch.setenv("CS230_STREAM", "auto")
    out = run_trials(kern, data, plan, params)
    assert len(out.trial_metrics) == 1
    assert 0.0 <= out.trial_metrics[0]["accuracy"] <= 1.0


def test_oom_repro_rf_strict_budget(monkeypatch):
    """Same wall for the tree family: the prepared dict (f32 X + bin
    codes + edges) busts the strict budget single-shot; streaming the bin
    codes block-wise completes."""
    _strict_small_budget(monkeypatch)
    data = _logreg_data(n=1500, d=32, c=3)
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=0)
    kern = get_kernel("RandomForestClassifier")
    params = [{"n_estimators": 1, "max_depth": 3, "n_bins": 8,
               "random_state": 0}]

    monkeypatch.setenv("CS230_STREAM", "0")
    with pytest.raises(sc.StageBudgetExceeded):
        run_trials(kern, data, plan, params)

    sc.STAGE_CACHE.clear()
    monkeypatch.setenv("CS230_STREAM", "auto")
    out = run_trials(kern, data, plan, params)
    assert len(out.trial_metrics) == 1


def test_strict_raise_leaves_no_cache_residue(monkeypatch):
    monkeypatch.setenv("CS230_STAGE_STRICT", "1")
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "0.1")
    with pytest.raises(sc.StageBudgetExceeded):
        sc.STAGE_CACHE.get_or_stage(
            ("fp", "dev", "huge"), lambda: np.zeros(200_000, np.float32)
        )
    stats = sc.STAGE_CACHE.stats()
    assert stats["entries"] == 0 and stats["bytes"] == 0
    # the key is free again: a smaller retry stages fine
    val, outcome = sc.STAGE_CACHE.get_or_stage(
        ("fp", "dev", "huge"), lambda: np.zeros(8, np.float32)
    )
    assert outcome == "miss" and val.shape == (8,)


def test_overflow_counter_and_event(monkeypatch):
    """All-pinned overflow (satellite fix): a cache forced over budget by
    pinned entries now EMITS tpuml_stage_cache_overflow_total and a
    stage.overflow flight-recorder event instead of overflowing silently."""
    monkeypatch.setenv("CS230_OBS", "1")
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "0.1")  # 100 KB
    before = REGISTRY.counter("tpuml_stage_cache_overflow_total").value()
    seq = RECORDER.last_seq()
    token = sc.STAGE_CACHE.pin_begin()
    try:
        for i in range(3):  # 3 x 60 KB pinned = 180 KB > 100 KB
            sc.STAGE_CACHE.get_or_stage(
                ("fp", "dev", f"pinned{i}"),
                lambda: np.zeros(15_000, np.float32),
            )
    finally:
        sc.STAGE_CACHE.pin_end(token)
    after = REGISTRY.counter("tpuml_stage_cache_overflow_total").value()
    assert after > before
    events, _ = RECORDER.events(since=seq)
    kinds = [e for e in events if e["kind"] == "stage.overflow"]
    assert kinds and kinds[-1]["data"]["reason"] == "pinned"
    assert kinds[-1]["data"]["overflow_bytes"] > 0


# ---------------- streamer mechanics ----------------


def _block_streamer(arr, plan, cache=None, **kw):
    return st.RowBlockStreamer(
        ("fp", ("cpu", 0), "block", "t"),
        st.array_block_source(arr, plan),
        lambda b: jnp.asarray(b),
        plan,
        cache=cache if cache is not None else sc.STAGE_CACHE,
        row_shape=arr.shape[1:],
        **kw,
    )


def test_streamer_yields_all_blocks_in_order_with_parity():
    rng = np.random.default_rng(0)
    arr = rng.normal(size=(1000, 8)).astype(np.float32)
    plan = st.plan_blocks(1000, row_bytes=32, rows=256)
    s = _block_streamer(arr, plan)
    got = []
    for i, start, blk in s.iter_blocks():
        assert start == plan.start(i)
        got.append(np.asarray(blk)[: plan.size(i)])
    assert np.array_equal(np.concatenate(got), arr)
    assert s.stats["passes"] == 1 and s.stats["uploads"] == plan.n_blocks
    # pass 2 is all cache hits
    for _ in s.iter_blocks():
        pass
    assert s.stats["uploads"] == plan.n_blocks
    assert s.stats["blocks"] == 2 * plan.n_blocks


def test_prefetch_pin_survives_lru_pressure(monkeypatch):
    """While a pass runs, the in-flight and prefetched blocks hold cache
    refs: junk staged between yields evicts only CONSUMED blocks, so no
    block is uploaded twice within the pass and every yielded value is
    intact (double-buffer on)."""
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "0.3")  # ~2 blocks of slack
    rng = np.random.default_rng(1)
    arr = rng.normal(size=(2048, 16)).astype(np.float32)  # 128 KB total
    plan = st.plan_blocks(2048, row_bytes=64, rows=256)   # 16 KB blocks
    s = _block_streamer(arr, plan, double_buffer=True)
    junk = 0
    for i, start, blk in s.iter_blocks():
        assert np.array_equal(np.asarray(blk), arr[start : start + 256])
        # LRU pressure from a concurrent tenant between every yield
        junk += 1
        sc.STAGE_CACHE.get_or_stage(
            ("fp2", "dev", "junk", junk),
            lambda: np.zeros(50_000, np.float32),  # 200 KB each
        )
    assert s.stats["uploads"] == plan.n_blocks  # nothing re-uploaded mid-pass


def test_two_tenants_share_block_uploads():
    """Two concurrent streamers over the same base key single-flight every
    block: exactly ONE upload per block key."""
    rng = np.random.default_rng(2)
    arr = rng.normal(size=(1024, 8)).astype(np.float32)
    plan = st.plan_blocks(1024, row_bytes=32, rows=256)
    barrier = threading.Barrier(2)
    sums = []

    def tenant():
        s = _block_streamer(arr, plan)
        barrier.wait()
        tot = 0.0
        for i, start, blk in s.iter_blocks():
            tot += float(np.asarray(blk).sum())
        sums.append(tot)

    threads = [threading.Thread(target=tenant) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(sums) == 2 and sums[0] == sums[1]
    uploads = sc.STAGE_CACHE.uploads_by_key()
    block_keys = [k for k in uploads if "block" in k]
    assert len(block_keys) == plan.n_blocks
    assert all(uploads[k] == 1 for k in block_keys)


def test_per_host_disjoint_block_sets(eight_device_mesh):
    """The 2-D "rows" mesh staging form generalized to block sets: each
    simulated host streams only its host_block_set slice under its own
    host_signature-keyed entries — no key collisions, full coverage."""
    rng = np.random.default_rng(3)
    arr = rng.normal(size=(1600, 4)).astype(np.float32)
    plan = st.plan_blocks(1600, row_bytes=16, rows=256)
    n_shards = 2
    rows_seen = []
    for shard in range(n_shards):
        ids = st.host_block_set(plan.n_blocks, n_shards, shard)
        s = st.RowBlockStreamer(
            ("fp", ("cpu", shard), "block", "t"),
            st.array_block_source(arr, plan),
            lambda b: jnp.asarray(b),
            plan,
            block_ids=ids,
            cache=sc.STAGE_CACHE,
            row_shape=(4,),
        )
        for i, start, blk in s.iter_blocks():
            assert i in ids
            rows_seen.append((start, plan.size(i)))
        assert s.stats["blocks"] == len(ids)
    assert sum(size for _, size in rows_seen) == 1600
    # per-host key namespaces never collide
    keys = [k for k in sc.STAGE_CACHE.uploads_by_key() if "block" in k]
    assert len(keys) == plan.n_blocks
    assert {k[1] for k in keys} == {("cpu", 0), ("cpu", 1)}


def test_double_buffer_off_still_correct(monkeypatch):
    monkeypatch.setenv("CS230_STREAM_DOUBLE_BUFFER", "0")
    rng = np.random.default_rng(4)
    arr = rng.normal(size=(700, 8)).astype(np.float32)
    plan = st.plan_blocks(700, row_bytes=32, rows=256)
    s = _block_streamer(arr, plan)
    got = [np.asarray(b)[: plan.size(i)] for i, _, b in s.iter_blocks()]
    assert np.array_equal(np.concatenate(got), arr)


# ---------------- chunked CSV ingest ----------------


def test_csv_chunked_ingest_round_trip(tmp_path):
    pd = pytest.importorskip("pandas")
    from cs230_distributed_machine_learning_tpu.data.download import (
        iter_csv_chunks,
    )
    from cs230_distributed_machine_learning_tpu.data.preprocess import (
        chunked_column_stats, iter_design_blocks,
    )

    rng = np.random.default_rng(6)
    n = 333
    df = pd.DataFrame({
        "a": rng.normal(2.0, 3.0, size=n),
        "b": rng.normal(-1.0, 0.5, size=n),
        "label": rng.integers(0, 2, size=n),
    })
    path = tmp_path / "toy.csv"
    df.to_csv(path, index=False)

    # pass 1: streaming stats match the whole-frame values
    stats = chunked_column_stats(
        iter_csv_chunks(str(path), chunk_rows=50), columns=["a", "b"]
    )
    for c in ("a", "b"):
        assert stats[c]["count"] == n
        assert abs(stats[c]["mean"] - df[c].mean()) < 1e-9
        assert abs(stats[c]["std"] - df[c].std(ddof=0)) < 1e-9

    # pass 2: standardized design blocks through CsvBlockSource
    def open_blocks():
        return iter_design_blocks(
            iter_csv_chunks(str(path), chunk_rows=50),
            stats=stats, target_column="label",
        )

    plan = st.plan_blocks(n, row_bytes=8, rows=64)
    src = st.CsvBlockSource(open_blocks, plan)
    got = [src.fetch(i)[: plan.size(i)] for i in plan.block_ids()]
    ref = np.stack(
        [(df[c] - stats[c]["mean"]) / stats[c]["std"] for c in ("a", "b")], 1
    ).astype(np.float32)
    assert np.allclose(np.concatenate(got), ref, atol=1e-6)

    # rewind (new pass) and skip-ahead (per-host block sets) both work
    assert np.allclose(src.fetch(0)[: plan.size(0)], ref[:64], atol=1e-6)
    tail = plan.n_blocks - 1
    assert np.allclose(
        src.fetch(tail)[: plan.size(tail)], ref[plan.start(tail):], atol=1e-6
    )


# ---------------- the block height counts the lanes (PR 40) ----------------

#: the cell logreg_mnist8m.rs32: 1.6M rows of 784 float32 pixels, 10
#: classes, 32 trials x 6 splits, on a v5e (bytes_limit about 16.9 GB, the
#: stage budget 0.4 of it)
_CELL_N, _CELL_ROW_BYTES, _CELL_LANES, _V5E_BUDGET_MB = 1_600_000, 784 * 4, 32 * 6, 0.4 * 16.9e3


def _lane_row_bytes(n_classes=10):
    kern = get_kernel("LogisticRegression")
    return kern.stream_lane_row_bytes({"_n_classes": n_classes})


def test_block_plan_counts_the_lanes_intermediates(monkeypatch):
    """At the cell's shape the block's own eighth of the budget binds and
    the planned intermediates of its 192 lanes fit the work budget; past it
    the lanes decide, and a block shrinks as they grow."""
    monkeypatch.delenv("CS230_STREAM_BLOCK_ROWS", raising=False)
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", str(_V5E_BUDGET_MB))
    lane = _lane_row_bytes()
    assert lane == 10 * 6 + 12
    plan = st.plan_blocks(_CELL_N, _CELL_ROW_BYTES, work_row_bytes=_CELL_LANES * lane)
    assert plan.rows == int(_V5E_BUDGET_MB * 1e6 // 8 // _CELL_ROW_BYTES) and plan.n_blocks == 6
    assert plan.rows * _CELL_LANES * lane <= st.work_budget_bytes()
    assert plan.n_blocks * plan.rows * _CELL_ROW_BYTES <= sc.budget_bytes()  # the cache holds it
    rows = [st.plan_blocks(_CELL_N, _CELL_ROW_BYTES, work_row_bytes=lanes * lane).rows
            for lanes in (192, 384, 768, 1536)]
    assert rows[0] > rows[1] > rows[2] > rows[3]
    for lanes, r in zip((384, 768, 1536), rows[1:]):
        assert r == st.work_budget_bytes() // (lanes * lane)
    # without lanes (a kernel that names none) the height is the block's alone
    assert st.plan_blocks(_CELL_N, _CELL_ROW_BYTES).rows == rows[0]


def test_streamed_engine_sizes_its_blocks_by_the_chunks_lanes(monkeypatch):
    """``_run_streamed`` hands the kernel's lane bytes x the chunk's trials
    x splits to the plan: a tight stage budget makes the lanes bind, and
    the dispatch span says the height it chose."""
    from cs230_distributed_machine_learning_tpu.obs import TRACER
    from cs230_distributed_machine_learning_tpu.obs.tracing import span

    monkeypatch.setenv("CS230_STREAM", "force")
    monkeypatch.delenv("CS230_STREAM_BLOCK_ROWS", raising=False)
    monkeypatch.setenv("CS230_STAGE_CACHE_MB", "8")
    data = _logreg_data(n=4000, d=128, c=10)
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=2)
    kern = get_kernel("LogisticRegression")
    params = [{"C": 10.0 ** -k, "max_iter": 2} for k in range(16)]
    with span("test.stream") as root:
        run_trials(kern, data, plan, params)
    said = [s["attrs"] for s in TRACER.spans_for(root.trace_id) if s["name"] == "executor.dispatch"]
    lanes = 16 * plan.n_splits
    want = st.plan_blocks(4000, 128 * 4, work_row_bytes=lanes * _lane_row_bytes())
    assert want.rows < st.plan_blocks(4000, 128 * 4).rows  # the lanes bind here
    assert said[0]["block_rows"] == want.rows and said[0]["n_blocks"] == want.n_blocks == 3


def test_streamed_passes_are_spans_and_the_curve_comes_back(monkeypatch):
    """One ``stream.pass`` a pass (31 power, a step each, one eval) under the
    chunk's ``executor.dispatch`` (engine=streamed with the block plan), a
    device wait a step, each block program's compile at its first call, the
    fetch after; the cache-hit
    counter; ``run_time`` the device waits alone; and the learning curve at
    the packed path's slots, its steps those the solver ran."""
    from cs230_distributed_machine_learning_tpu.obs import TRACER
    from cs230_distributed_machine_learning_tpu.obs.tracing import span

    from cs230_distributed_machine_learning_tpu.models import logistic

    monkeypatch.setattr(logistic, "_STREAM_FN_CACHE", {})  # the programs built afresh
    monkeypatch.setenv("CS230_STREAM", "force")
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "512")
    data = _logreg_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=2)
    kern = get_kernel("LogisticRegression")
    params = [{"C": 1.0, "max_iter": 20}, {"C": 0.1, "max_iter": 12}]
    hits0 = REGISTRY.counter("tpuml_stream_cache_hits_total").value()
    with span("test.stream") as root:
        out = run_trials(kern, data, plan, params)
    spans = TRACER.spans_for(root.trace_id)
    (disp,) = [s for s in spans if s["name"] == "executor.dispatch"]
    assert disp["attrs"]["engine"] == "streamed"
    assert {k: disp["attrs"][k] for k in ("block_rows", "n_blocks", "split_lanes", "n_trials")} == {
        "block_rows": 512, "n_blocks": 3, "split_lanes": 3, "n_trials": 2}
    kids = [s for s in spans if s["parent_id"] == disp["span_id"]]
    passes = [s for s in kids if s["name"] == "stream.pass"]
    kinds = [p["attrs"]["kind"] for p in passes]
    assert kinds == ["power"] * 31 + ["step"] * 20 + ["eval"]
    assert all(p["attrs"]["blocks"] == 3 for p in passes)
    # the first pass uploads the three blocks, every later one hits them
    assert passes[0]["attrs"]["uploaded_bytes"] == 3 * 512 * 128 * 4
    assert passes[0]["attrs"]["cache_hits"] == 0
    assert all(p["attrs"]["cache_hits"] == 3 and p["attrs"]["uploaded_bytes"] == 0 for p in passes[1:])
    assert REGISTRY.counter("tpuml_stream_cache_hits_total").value() - hits0 == 3 * (len(passes) - 1)
    assert all(p["attrs"]["wait_s"] >= 0 and p["attrs"]["dispatch_s"] > 0 for p in passes)
    waits = [s for s in kids if s["name"] == "executor.wait"]
    assert len(waits) == 20 and {w["attrs"]["on"] for w in waits} == {"result"}
    (fetch,) = [s for s in spans if s["name"] == "executor.fetch"]  # after the dispatch
    assert fetch["start"] >= disp["end"]
    assert fetch["attrs"]["bytes"] > 0
    # each of the six block programs compiled once, at its first call, inside the dispatch
    compiles = [s for s in spans if s["name"] == "executor.compile"]
    assert len(compiles) == 6 and all(
        s["attrs"] == {"engine": "streamed", "cache": "traced"} and disp["start"] <= s["start"]
        and s["end"] <= disp["end"] for s in compiles)
    assert all(any(b["parent_id"] == s["span_id"] and b["attrs"]["stage"] == "compile"
                   for b in spans if b["name"] == "executor.build") for s in compiles)
    fetch_wait = [s for s in spans if s["parent_id"] == fetch["span_id"] and s["name"] == "executor.wait"]
    device_waits = sum(s["end"] - s["start"] for s in waits + fetch_wait)
    # the waits inside their spans: never more than the spans, less only by
    # the spans' own bookkeeping
    assert 0.8 * device_waits - 0.01 <= out.run_time_s <= device_waits
    # the curve: stride 1 at 20 steps, a slot a step, the trial's own rows
    for m, p in zip(out.trial_metrics, params):
        curve = m["curve"]
        assert curve["stride"] == 1 and curve["steps"] == 20
        assert len(curve["gmax"]) == plan.n_splits
        assert all(len(row) == 20 and all(v is not None and v > 0 for v in row) for row in curve["gmax"])
        assert curve["tail"][1:] == m["cv_scores"]


def test_streamed_curve_stops_where_the_solver_stopped(monkeypatch):
    """Every lane idle before the step cap: the solver leaves its loop and
    the curve keeps only the slots it reached."""
    monkeypatch.setenv("CS230_STREAM", "force")
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "512")
    data = _logreg_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=2)
    kern = get_kernel("LogisticRegression")
    # a huge tol: every lane is done after its first step
    out = run_trials(kern, data, plan, [{"C": 1.0, "max_iter": 20, "tol": 1e9}])
    curve = out.trial_metrics[0]["curve"]
    assert curve["steps"] == 1 and all(len(row) == 1 for row in curve["gmax"])


def test_streamed_curves_off_is_scores_alone(monkeypatch):
    monkeypatch.setenv("CS230_STREAM", "force")
    monkeypatch.setenv("CS230_STREAM_BLOCK_ROWS", "512")
    monkeypatch.setenv("CS230_CURVES", "0")
    data = _logreg_data()
    plan = build_split_plan(np.asarray(data.y), task="classification", n_folds=2)
    out = run_trials(get_kernel("LogisticRegression"), data, plan, [{"C": 1.0, "max_iter": 5}])
    assert "curve" not in out.trial_metrics[0] and len(out.trial_metrics[0]["cv_scores"]) == 2
