"""Measure the CS230_STAGE_DTYPE compressed-staging path.

PR 1 built bf16/int8 staging compression for the cold-start upload. This
harness measures, per CS230_STAGE_DTYPE mode, on the flagship covertype
design matrix:

- ``bytes_on_link``   — exact size of the host-side compressed form that
                        ``device_put`` ships (backend-independent: this is
                        the number that divides by the link bandwidth);
- ``compress_ms``     — host-side ``_stage_compress`` wall (the CPU cost
                        paid before the upload can start);
- ``upload_ms_measured`` — ``device_put`` + block wall on THIS backend's
                        REAL link (median of reps; no model);
- ``decode_roundtrip_max_abs`` — |decode(compress(X)) - X| bound (the
                        score-tolerance contract pinned in
                        tests/test_packed_parity.py).

It also measures the link bandwidth the ``CS230_STAGE_DTYPE=auto`` policy
probes (``trial_map._measured_link_mbps``: one 4 MiB device_put) and
reports which staging dtype ``auto`` resolves to on this link against the
``CS230_STAGE_AUTO_MBPS`` threshold.

Writes benchmarks/STAGING_MICRO.json.

Usage: python benchmarks/staging_micro.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from cs230_distributed_machine_learning_tpu.parallel.trial_map import (  # noqa: E402
    _measured_link_mbps,
    _resolve_stage_mode,
    _stage_compress,
    _stage_decode,
)

REPS = int(os.environ.get("STAGE_REPS", 5))
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "STAGING_MICRO.json")


def _nbytes(staged) -> int:
    if isinstance(staged, dict):
        return sum(int(np.asarray(v).nbytes) for v in staged.values())
    return int(np.asarray(staged).nbytes)


def main() -> None:
    from cs230_distributed_machine_learning_tpu.data.datasets import DatasetCache

    X = np.asarray(DatasetCache().get("covertype", "classification").X,
                   np.float32)
    scale_ref = np.abs(X).max(axis=0) + 1e-30
    modes = {}
    for mode in ("f32", "bf16", "int8"):
        walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            staged = _stage_compress(X, mode)
            walls.append(time.perf_counter() - t0)
        nbytes = _nbytes(staged)
        uploads = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            dev = jax.device_put(staged)
            jax.block_until_ready(dev)
            uploads.append(time.perf_counter() - t0)
        decoded = np.asarray(_stage_decode(jax.device_put(staged)))
        err = np.abs(decoded - X).max()
        rel = float((np.abs(decoded - X) / scale_ref[None, :]).max())
        modes[mode] = {
            "bytes_on_link": nbytes,
            "compress_ms": round(float(np.median(walls)) * 1e3, 2),
            "upload_ms_measured": round(float(np.median(uploads)) * 1e3, 2),
            "upload_mb_per_s_measured": round(
                nbytes / max(float(np.median(uploads)), 1e-9) / 1e6, 1
            ),
            "decode_roundtrip_max_abs": float(err),
            "decode_roundtrip_max_rel_to_col_scale": rel,
        }
    f32_bytes = modes["f32"]["bytes_on_link"]

    # streamed-tile row (PR 16): the same matrix shipped as row blocks
    # through the double-buffered streamer instead of one device_put —
    # per-block upload wall, aggregate vs single-shot, and the hidden
    # fraction when a per-block compute runs behind the prefetcher
    from cs230_distributed_machine_learning_tpu.data.stage_cache import (
        StagedDatasetCache,
    )
    from cs230_distributed_machine_learning_tpu.data.streaming import (
        RowBlockStreamer, array_block_source, plan_blocks,
    )
    import jax.numpy as jnp

    bplan = plan_blocks(X.shape[0], row_bytes=X.shape[1] * 4, rows=16384)

    @jax.jit
    def _touch(blk):
        if isinstance(blk, dict):  # compressed staged form
            blk = _stage_decode(blk)
        return jnp.tanh(blk).sum()

    streamed_tiles = {
        "block_rows": bplan.rows,
        "n_blocks": bplan.n_blocks,
        "single_shot_upload_ms_measured":
            modes["f32"]["upload_ms_measured"],
        "modes": {},
        "note": (
            "row-block streaming (data/streaming.py) over the same "
            "matrix, per CS230_STAGE_DTYPE block form: the pass pays "
            "per-block device_puts but hides them behind the per-block "
            "compute; block_upload_mb_per_s_measured is bytes_on_link / "
            "upload wall — the effective per-block link bandwidth. The "
            "full overlap study is benchmarks/STREAMING_MICRO.json"
        ),
    }
    for smode in ("f32", "bf16", "int8"):
        def _ship(b, _m=smode):
            staged = _stage_compress(np.ascontiguousarray(b), _m)
            return jax.tree_util.tree_map(jnp.asarray, staged) \
                if isinstance(staged, dict) else jnp.asarray(staged)

        jax.block_until_ready(
            _touch(_ship(np.zeros((bplan.rows, X.shape[1]), np.float32)))
        )
        tile_walls, hidden_fracs, upload_ws, link_bytes = [], [], [], []
        for _ in range(REPS):
            streamer = RowBlockStreamer(
                ("staging_micro", ("bench", 0), "block", "tiles", smode),
                array_block_source(X, bplan),
                _ship,
                bplan,
                double_buffer=True,
                cache=StagedDatasetCache(),  # fresh: every block uploads
                row_shape=(X.shape[1],),
            )
            t0 = time.perf_counter()
            for _i, _s, blk in streamer.iter_blocks():
                _touch(blk)
            tile_walls.append(time.perf_counter() - t0)
            st = streamer.stats
            upload_ws.append(st["upload_s"])
            link_bytes.append(st["bytes"])
            hf = streamer.hidden_fraction()
            if hf is not None:
                hidden_fracs.append(hf)
        up_s = float(np.median(upload_ws))
        nbytes_link = float(np.median(link_bytes))
        streamed_tiles["modes"][smode] = {
            "block_mb_on_link": round(
                nbytes_link / max(bplan.n_blocks, 1) / 1e6, 2
            ),
            "pass_wall_ms_measured": round(
                float(np.median(tile_walls)) * 1e3, 2
            ),
            "block_upload_ms_measured": round(
                up_s / max(bplan.n_blocks, 1) * 1e3, 2
            ),
            "block_upload_mb_per_s_measured": round(
                nbytes_link / max(up_s, 1e-9) / 1e6, 1
            ),
            "hidden_frac_double_buffered": round(
                float(np.median(hidden_fracs)), 4
            ) if hidden_fracs else None,
        }

    # the auto-policy probe: the same 4 MiB device_put measurement
    # run_trials consults when CS230_STAGE_DTYPE=auto picks a dtype
    link_mbps = _measured_link_mbps()
    auto_threshold = float(os.environ.get("CS230_STAGE_AUTO_MBPS", 100.0))
    os.environ["CS230_STAGE_DTYPE"] = "auto"
    auto_resolved = _resolve_stage_mode("auto")
    out = {
        "metric": "compressed_staging_micro",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "dataset": f"covertype {X.shape[0]}x{X.shape[1]} f32",
        "link_probe_mb_per_s_measured": round(link_mbps, 1)
        if link_mbps != float("inf") else None,
        "auto_policy": {
            "threshold_mb_per_s": auto_threshold,
            "resolves_to": auto_resolved,
            "rule": "bf16 when measured link < threshold, else f32",
        },
        "modes": modes,
        "streamed_tiles": streamed_tiles,
        "saving_vs_f32": {
            m: round(1.0 - v["bytes_on_link"] / f32_bytes, 3)
            for m, v in modes.items() if "bytes_on_link" in v
        },
        "note": (
            "CS230_STAGE_DTYPE staging measured for real on THIS "
            "backend's link (upload_ms_measured / "
            "upload_mb_per_s_measured are device_put+block medians, not "
            "a model). The auto policy's probe measured "
            "link_probe_mb_per_s_measured and resolves as reported. "
            "bytes_on_link ratios stay the robust number: bf16 halves, "
            "int8 quarters whatever the link delivers."
        ),
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
