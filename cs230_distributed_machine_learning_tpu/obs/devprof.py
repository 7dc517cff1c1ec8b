"""Device-time attribution + on-demand deep profiling.

Four rounds of kernel/data-plane work are valve-gated and CPU-verified
while the flagship number sits flat — the missing layer is knowing, on a
LIVE system, where device time actually goes. Two instruments:

- **Per-phase device-seconds**: every executed batch's measured phase
  totals (the trial engine's ``compile`` / ``stage`` / ``dispatch`` /
  ``fetch`` timers, already derived from ``block_until_ready`` deltas
  around each dispatch) accumulate into
  ``tpuml_executor_device_seconds_total{phase=}`` — a *counter*, so the
  embedded time-series ring (obs/timeseries.py) samples it for free and
  ``/dashboard`` can draw a device-seconds-per-second-by-phase rate with
  no new sampling machinery. The executor feeds it for local batches
  (:func:`record_batch_device_seconds`) and the coordinator's
  ``push_metrics`` ingest feeds it for remote agents' batches (same
  ``batch_primary`` + ``obs_pid`` dedup contract as the phase
  histograms — docs/OBSERVABILITY.md).
- **Programmatic ``jax.profiler`` capture**: ``POST /profile/start`` /
  ``POST /profile/stop`` (runtime/server.py) bracket a live workload with
  a real XLA trace dumped under ``<journal_dir>/profile/<tag>`` — the
  one deep-inspection path; the program's spans land in it as
  ``tpuml.<span name>`` host rows (obs/tracing.py). One capture at a time;
  start/stop land in the flight recorder (``profile.start`` /
  ``profile.stop``) so the capture window is visible next to the
  scheduling decisions it brackets.

Everything is valve-gated by ``CS230_OBS`` like the rest of ``obs/``:
disabled, the recorder helpers return after one env read and profile
capture refuses to start.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional

from .metrics import REGISTRY
from .recorder import record_event
from .tracing import _enabled, journal_dir

#: the attribution phases, in pipeline order. ``dispatch`` is the device
#: execution window minus the blocking fetches it contains — the four
#: batch phases sum to (compile + stage + run) wall, not double-counting
#: fetch. ``stream`` is the out-of-core overlap phase: the share of a
#: streaming pass's host->device transfer wall HIDDEN behind compute by
#: the double-buffered uploader (data/streaming.py) — the blocking
#: remainder rides the engine's ordinary ``stage`` accumulator, so
#: stage + stream together are the full streamed-transfer wall.
PHASES = ("stage", "compile", "dispatch", "fetch", "stream")

DEVICE_SECONDS = "tpuml_executor_device_seconds_total"


def device_seconds(phase: str, seconds: float) -> None:
    """Accumulate ``seconds`` of device/pipeline time into ``phase``.

    No-op when ``CS230_OBS=0`` or the duration is non-positive (phases a
    batch never entered — e.g. a fully cache-hit stage — add nothing
    rather than minting zero-valued cells churn)."""
    if not _enabled():
        return
    s = float(seconds)
    if s <= 0.0:
        return
    REGISTRY.counter(DEVICE_SECONDS).inc(s, phase=phase)


def record_batch_device_seconds(
    compile_s: float, stage_s: float, run_s: float, fetch_s: float
) -> None:
    """Attribute one executed batch's phase totals (TrialRunResult's
    timers). ``dispatch`` = the device window minus the blocking fetches
    inside it, clamped at zero."""
    if not _enabled():
        return
    device_seconds("compile", compile_s)
    device_seconds("stage", stage_s)
    device_seconds("dispatch", max(float(run_s) - float(fetch_s), 0.0))
    device_seconds("fetch", fetch_s)


def phase_totals() -> Dict[str, float]:
    """Current per-phase accumulations (tests, chip_smoke.py)."""
    c = REGISTRY.counter(DEVICE_SECONDS)
    return {p: c.value(phase=p) for p in PHASES}


class DeviceProfiler:
    """One-at-a-time programmatic ``jax.profiler`` capture.

    ``start()`` opens a trace into ``<journal_dir>/profile/<tag>`` and
    ``stop()`` closes it; both record flight-recorder events and feed
    ``tpuml_profile_captures_total``. A second ``start()`` while a capture
    is open is refused (the profiler is process-global state) — callers
    get a structured error instead of a jax exception."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._active: Optional[Dict[str, Any]] = None

    def status(self) -> Dict[str, Any]:
        with self._lock:
            if self._active is None:
                return {"active": False}
            return {"active": True, **self._active}

    def start(self, tag: Optional[str] = None) -> Dict[str, Any]:
        """Begin a capture. Returns ``{status: "started", trace_dir: ...}``
        or a structured error dict (``status: "error"``) whose ``reason``
        tells the transport layer what happened: ``disabled`` (valve off
        → 503), ``busy`` (capture already open → 409), or ``backend``
        (the profiler/filesystem refused → 500)."""
        if not _enabled():
            return {
                "status": "error",
                "reason": "disabled",
                "message": "observability disabled (CS230_OBS=0)",
            }
        tag = _sanitize_tag(tag) or time.strftime("%Y%m%d-%H%M%S")
        trace_dir = os.path.join(journal_dir(), "profile", tag)
        with self._lock:
            if self._active is not None:
                return {
                    "status": "error",
                    "reason": "busy",
                    "message": "capture already active",
                    **self._active,
                }
            try:
                import jax

                os.makedirs(trace_dir, exist_ok=True)
                jax.profiler.start_trace(trace_dir)
            except Exception as e:  # noqa: BLE001 — surface, don't crash the server
                return {"status": "error", "reason": "backend",
                        "message": f"{type(e).__name__}: {e}"}
            self._active = {
                "tag": tag,
                "trace_dir": trace_dir,
                "started_ts": time.time(),
            }
            info = dict(self._active)
        record_event("profile.start", tag=tag, trace_dir=trace_dir)
        return {"status": "started", **info}

    def stop(self) -> Dict[str, Any]:
        """Finish the active capture. Returns ``{status: "stopped",
        trace_dir, duration_s, n_files}`` or an error when none is
        active. A FAILED stop (e.g. the dump filesystem filled up) keeps
        the capture marked active so it can be retried — unless the
        backend reports no session is running, in which case the handle
        is cleared (nothing is left to stop)."""
        with self._lock:
            if self._active is None:
                return {"status": "error", "reason": "idle",
                        "message": "no active capture"}
            info = self._active
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                # jax's "no profile/trace running" means the session died
                # underneath us — clearing the handle is the only way out;
                # any other failure keeps it active for a retry
                session_gone = "no profile" in str(e).lower() or \
                    "no trace" in str(e).lower()
                if session_gone:
                    self._active = None
                record_event("profile.stop", tag=info["tag"], error=str(e))
                return {"status": "error",
                        "reason": "idle" if session_gone else "backend",
                        "message": f"{type(e).__name__}: {e}",
                        **info}
            self._active = None
        duration = time.time() - info["started_ts"]
        n_files = sum(len(fs) for _, _, fs in os.walk(info["trace_dir"]))
        REGISTRY.counter(
            "tpuml_profile_captures_total",
        ).inc()
        record_event(
            "profile.stop", tag=info["tag"], trace_dir=info["trace_dir"],
            duration_s=round(duration, 3), n_files=n_files,
        )
        return {
            "status": "stopped",
            "tag": info["tag"],
            "trace_dir": info["trace_dir"],
            "duration_s": duration,
            "n_files": n_files,
        }


def _sanitize_tag(tag: Optional[str]) -> Optional[str]:
    """Capture tags come off the wire and become a path component: keep
    [-._a-zA-Z0-9] only, so a request cannot traverse out of the journal
    dir."""
    if not tag:
        return None
    clean = "".join(c for c in str(tag) if c.isalnum() or c in "-._")
    return clean.strip(".") or None


#: the process-global profiler the /profile routes drive
PROFILER = DeviceProfiler()
