"""Whether the compiled packed LogReg step kernel gives the same bits with
the occupancy table as without it, at the cell's shape, on this backend.

    python3 perfbench/tools/probe_logreg_skip_parity.py \\
        --workload logreg_rows5m.rs128 --seed 7

The cell's table and labels, the program's own fold plan (split 0 a random
80%, splits 1..5 unshuffled stratified folds: a fifth of the row tiles
empty for each) and the same folds dealt to permuted rows (``shuffled``:
nothing to skip). Two readings:

* *step*: ``packed_nesterov_step`` scanned over ``--steps`` steps at one
  and at two weight blocks, with ``occ=None`` (the whole slab on every
  tile: the body of ``_grad_kernel``, the parity reference), with the
  plan's table and with an all-ones table; ``W``, ``Wp`` and the ``gmax``
  of every step compared bit for bit against the first (``BIT_EQUAL``
  lines), and each form's warm seconds. The interpreter passed a form of
  this kernel that was wrong compiled (the kernel's docstring), so this is
  the comparison that counts; tier-1 runs it on the CPU at toy shapes.
* *fit*: the whole ``build_batched_fn`` (100 steps and the scoring) on
  both plans, warm seconds: the shuffled plan's reading is what the
  narrower-slab form costs where it skips nothing. Run from a checkout of
  an older program for the other side of that comparison: one without the
  table prints its fit seconds alone.

The arrays are passed as arguments, never closed over: a jit that closes
over 5M-row arrays embeds them as constants. Three minutes on the chip.
Decides nothing in ``correct``; exits 1 where a comparison is not equal.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402  (perfbench/run.py)


def warm_seconds(f, *args):
    import jax

    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    return out, time.perf_counter() - t0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="logreg_rows5m.rs128")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rows", type=int, default=0, help="cut the table (CPU rehearsals)")
    ap.add_argument("--skip-fit", action="store_true", help="the step readings only")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from cs230_distributed_machine_learning_tpu.models.logistic import _packed_geometry
    from cs230_distributed_machine_learning_tpu.models.registry import get_kernel
    from cs230_distributed_machine_learning_tpu.ops import pallas_logreg as pk
    from cs230_distributed_machine_learning_tpu.ops.folds import build_split_plan
    from cs230_distributed_machine_learning_tpu.utils import backend

    interpret = backend.pallas_interpret()
    cell = run.load_cell(args.workload, run.REPO_ROOT)
    X_np, y_np = run.make_dataset(cell, args.seed)
    if args.rows:
        X_np, y_np = X_np[: args.rows], y_np[: args.rows]
    (n, d), c = X_np.shape, int(cell["config"]["dataset"]["n_classes"])
    traffic = cell["traffic"]
    plan = build_split_plan(
        y_np, task="classification", n_folds=int(traffic["cv"]),
        test_size=float(traffic["test_size"]),
        random_state=int(traffic["split_random_state"]))
    S, chunk = plan.n_splits, int(traffic["n_iter"])
    perm = np.random.RandomState(args.seed % (2**31)).permutation(n)
    plans = {"stratified": (plan.train_w, plan.eval_w),
             "shuffled": (plan.train_w[:, perm], plan.eval_w[:, perm])}
    print("device", jax.devices()[0].device_kind, "rows", n, "splits", S, flush=True)

    kernel = get_kernel("LogisticRegression")
    static = kernel.resolve_static(dict(cell["config"]["estimator"]["params"]), n, d, c)
    static.update(_n_classes=c, _method="nesterov")
    static = kernel.bucket_static(static, [{"max_iter": 100}])
    geo = _packed_geometry(static, n, d, c, S)
    bm, dpp, n_pad = geo.get("bm", 256), geo["dpp"], geo["n_pad"]
    has_table = hasattr(pk, "tile_occupancy")
    out = {"rows": n, "splits": S, "bm": bm, "has_table": has_table}
    y = jnp.asarray(y_np)

    def padded(TW):
        return jnp.pad(jnp.asarray(TW), ((0, 0), (0, n_pad - n)))

    if has_table:
        for name, (TW, _) in plans.items():
            out[f"skip_pct_{name}"] = pk.tile_skip_pct(pk.tile_occupancy(padded(TW), bm=bm), S)

    if not args.skip_fit:
        X = jnp.asarray(X_np)
        fit = jax.jit(kernel.build_batched_fn(static, n, d, c, S, chunk))
        hyper = {"C": jnp.asarray(np.geomspace(1e-8, 1e2, chunk).astype(np.float32)),
                 "max_iter": jnp.full((chunk,), 100.0, jnp.float32),
                 "tol": jnp.full((chunk,), 1e-4, jnp.float32)}
        for name, (TW, EW) in plans.items():
            _, out[f"fit_warm_s_{name}"] = warm_seconds(
                fit, X, y, jnp.asarray(TW), jnp.asarray(EW), hyper)
            print("FIT", name, out.get(f"skip_pct_{name}"), out[f"fit_warm_s_{name}"], flush=True)
        del X, fit
    del X_np

    equal = True
    if has_table:
        Tw = 128
        B = pk.slab_lanes(S, Tw)
        TWp = padded(plans["stratified"][0])
        WSP, y2 = TWp.T, jnp.pad(y, (0, n_pad - n))[:, None]
        Ab = jax.random.normal(
            jax.random.PRNGKey(args.seed % (2**31)), (n_pad, dpp), jnp.float32
        ).astype(jnp.bfloat16)
        occ = pk.tile_occupancy(TWp, bm=bm)
        tables = {"unskipped": None, "table": occ,
                  "all_ones": jnp.full_like(occ, (1 << S) - 1)}
        for n_wb in (1, 2):
            steps = args.steps if n_wb == 1 else max(args.steps // 5, 2)
            Cb = jnp.asarray(np.geomspace(1e-4, 1e2, n_wb * B).astype(np.float32)).reshape(n_wb, B)
            step_b = jnp.full((n_wb, B), 2e-8, jnp.float32)
            maxit = jnp.full((n_wb, B), 1e9, jnp.float32)
            pen = jnp.ones((dpp, 1), jnp.float32)

            def scan(table, Ab, y2, WSP):
                W0 = jnp.zeros((n_wb, dpp, c * B), jnp.float32)
                done = jnp.zeros((n_wb, B), jnp.float32)

                def body(carry, t):
                    W, Wp, gmax = pk.packed_nesterov_step(
                        Ab, *carry, y2, WSP, t, done, step_b, Cb, maxit, pen, table,
                        c=c, S=S, Tw=Tw, bm=bm, lam=1.0, interpret=interpret)
                    return (W, Wp), gmax

                (W, Wp), gmax = jax.lax.scan(
                    body, (W0, W0), jnp.arange(steps, dtype=jnp.float32))
                return W, Wp, gmax

            got = {}
            for name, table in tables.items():
                res, secs = warm_seconds(jax.jit(scan), table, Ab, y2, WSP)
                got[name] = [np.asarray(x) for x in res]
                out[f"step_warm_s_{name}_nwb{n_wb}"] = secs
            moved = bool(np.abs(got["unskipped"][0]).max() > 0 and (got["unskipped"][2] != 0).any())
            for name in ("table", "all_ones"):
                eq = [bool(np.array_equal(a, b)) for a, b in zip(got[name], got["unskipped"])]
                out[f"bit_equal_{name}_nwb{n_wb}"] = eq
                equal = equal and all(eq) and moved
                print("BIT_EQUAL (W, Wp, gmax)", name, "n_wb", n_wb, "steps", steps, eq,
                      "weights moved", moved, "warm_s",
                      {k: round(out[f"step_warm_s_{k}_nwb{n_wb}"], 4) for k in tables}, flush=True)
    print(json.dumps(out), flush=True)
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
