"""Analytical FLOP accounting + MFU for the benchmark harnesses.

The reference has no utilization measurement at all (SURVEY.md §6); the
round-1 verdict flagged "is it actually fast for the silicon" as
unanswerable. Kernels publish ``macs_estimate(n, d, static)`` — the
model-analytical multiply-accumulate count of ONE (trial, split) fit — and
the harnesses combine it with wall-clock and the device's peak rate:

    mfu = (2 * macs * n_splits * n_trials) / wall_s / peak_flops

This is *model* FLOP utilization: only the FLOPs the model semantically
requires count, not implementation overheads (padding, recompute, masked
lanes), so it is comparable across implementations.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import backend as _backend


def analytical_flops(
    kernel: Any,
    static: Dict[str, Any],
    n: int,
    d: int,
    n_splits: int,
    n_trials: int,
) -> Optional[float]:
    """Total model FLOPs of a job: 2 * per-(trial,split) MACs * splits *
    trials. None when the kernel has no analytical estimate."""
    if not hasattr(kernel, "macs_estimate"):
        return None
    per = float(kernel.macs_estimate(n, d, static))
    return 2.0 * per * max(n_splits, 1) * max(n_trials, 1)


def stratified_by(population, key_fn, n_samples: int):
    """Evenly spaced quantile positions of ``population`` sorted by
    ``key_fn`` — the harnesses' shared subsampling for extrapolated sklearn
    denominators (per-trial cost varies strongly with e.g. C under
    loguniform, so random draws under-represent the tails)."""
    import numpy as np

    srt = sorted(population, key=key_fn)
    pos = (
        np.linspace(0, len(srt) - 1, min(n_samples, len(srt))).round().astype(int)
    )
    return [srt[i] for i in pos]


def mfu(
    flops: Optional[float], wall_s: float, n_devices: int = 1
) -> Optional[float]:
    """Achieved fraction of device peak; None off-accelerator or without an
    analytical FLOPs figure. ``n_devices`` scales the peak for work that
    ran across a mesh — whole-mesh FLOPs over a single chip's peak would
    report N x reality."""
    peak = _backend.device_peak_flops()
    if flops is None or peak is None or wall_s <= 0:
        return None
    return flops / wall_s / (peak * max(int(n_devices), 1))
