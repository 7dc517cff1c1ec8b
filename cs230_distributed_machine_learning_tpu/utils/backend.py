"""The one module that knows which platform this process computes on.

Every decision that depends on the backend lives here: whether an ``auto``
kernel valve selects its Pallas kernel, whether a ``pallas_call`` runs in
the interpreter, the device's peak rate and its memory budget. Kernels and
the trial engine ask these functions; nothing else in the package compares
a backend name or sets ``interpret=``. An accelerator this module does not
know is an error, never a default: a guessed peak or memory size would turn
every utilization figure and every chunk cap into fiction.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict, Optional, Sequence

#: host-memory budget (MB) the chunk planners use on the CPU backend, where
#: ``memory_stats()`` reports nothing
_CPU_BUDGET_MB = 8_000.0

#: peak dense bf16 FLOP/s by ``device_kind`` substring (published specs;
#: v5e: Google Cloud "TPU v5e" documentation, 197 TFLOP/s)
_PEAK_FLOPS = (
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6e", 918e12),
    ("v6 lite", 918e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

_scope = threading.local()


def name() -> str:
    """The platform the code being traced will run on: the process's
    default backend, except inside ``host_execution()``."""
    import jax

    return getattr(_scope, "platform", None) or jax.default_backend()


def on_cpu() -> bool:
    return name() == "cpu"


def on_tpu() -> bool:
    return name() == "tpu"


def pallas_interpret() -> bool:
    """``interpret=`` of every ``pallas_call`` in the package: the
    interpreter is test coverage for the CPU backend under
    ``CS230_PALLAS_INTERPRET=1`` and nothing else. On an accelerator a
    kernel compiles or the job fails; it never runs interpreted."""
    return os.environ.get("CS230_PALLAS_INTERPRET", "") == "1" and on_cpu()


@contextlib.contextmanager
def xla_formulations():
    """Trace-time scope in which no ``auto`` valve selects a Pallas
    kernel. The trial engine traces inside it every mesh executable that
    XLA has to partition: a Mosaic kernel under ``jit`` with mesh
    shardings cannot be partitioned ("wrap the call in a shard_map"), the
    XLA formulations can. Those are the generic vmapped fit and the
    chunked protocol of every family, and everything on a (trials, data)
    mesh. A kernel's ``build_batched_fn`` on a 1-D trial mesh is wrapped
    in that ``shard_map`` instead (``parallel/trial_map.py::
    _shard_batched``) and is traced outside this scope."""
    prev = getattr(_scope, "xla_only", False)
    _scope.xla_only = True
    try:
        yield
    finally:
        _scope.xla_only = prev


@contextlib.contextmanager
def host_execution():
    """Trace-time scope of a program the trial engine runs on the host CPU
    of an accelerator process (its small-bucket fast path): every decision
    of this module answers for the CPU — ``auto`` valves pick their CPU
    formulations and no Pallas kernel is selected. Without it a forest
    routed to the host on a TPU backend traced the Pallas histogram and
    failed in the CPU lowering."""
    prev = getattr(_scope, "platform", None)
    _scope.platform = "cpu"
    try:
        yield
    finally:
        _scope.platform = prev


def auto_pallas() -> bool:
    """Whether an ``auto`` kernel valve selects its Pallas kernel here:
    compiled on a TPU backend, interpreted on the CPU under
    ``CS230_PALLAS_INTERPRET=1`` for the paths that opt in themselves,
    never inside ``xla_formulations()``."""
    return on_tpu() and not getattr(_scope, "xla_only", False)


def device_peak_flops() -> Optional[float]:
    """Peak bf16 FLOP/s of device 0; None on the CPU backend (utilization
    is not a meaningful metric for host execution). Raises on an
    accelerator that is not in the table."""
    import jax

    if on_cpu():
        return None
    kind = str(jax.devices()[0].device_kind)
    for sub, peak in _PEAK_FLOPS:
        if sub in kind.lower():
            return peak
    raise RuntimeError(
        f"no peak FLOP/s known for device_kind={kind!r} on backend "
        f"{name()!r}: add it to utils/backend._PEAK_FLOPS"
    )


def device_memory_stats(device: Any = None) -> Dict[str, Any]:
    """``memory_stats()`` of ``device`` (default: local device 0); ``{}``
    on the CPU backend, which reports none."""
    import jax

    dev = device if device is not None else jax.local_devices()[0]
    return dict(dev.memory_stats() or {})


def hbm_peak_bytes(devices: Optional[Sequence[Any]] = None) -> Optional[int]:
    """High-water ``peak_bytes_in_use`` over ``devices`` (default: local
    device 0) — monotonic over the process lifetime; None on CPU."""
    import jax

    if on_cpu():
        return None
    peaks = [
        device_memory_stats(d).get("peak_bytes_in_use")
        for d in (devices or jax.local_devices()[:1])
    ]
    return max((int(p) for p in peaks if p is not None), default=None)


def device_memory_mb() -> float:
    """Per-device memory budget in MB for the chunk planners: the
    accelerator's ``bytes_limit`` (chips of one host are alike, so device 0
    speaks for the mesh), a fixed host budget on CPU. An accelerator that
    reports no limit raises."""
    if on_cpu():
        return _CPU_BUDGET_MB
    stats = device_memory_stats()
    if "bytes_limit" not in stats:
        raise RuntimeError(
            f"backend {name()!r} reports no memory_stats()['bytes_limit']"
            "; refusing to guess the device memory size"
        )
    return stats["bytes_limit"] / 1e6


def describe() -> Dict[str, Any]:
    """The device line every entry point prints: what JAX reports."""
    import jax

    devs = jax.devices()
    return {
        "backend": name(),
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
    }
