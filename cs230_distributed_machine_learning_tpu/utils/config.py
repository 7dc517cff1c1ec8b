"""Typed, layered configuration system.

Replaces the reference's three copy-pasted per-service ``config.py`` constant
files and env-var sprinkling (reference: ``aws-prod/master/config.py:1-18``,
``aws-prod/scheduler/scheduler.py:59-65``, ``aws-prod/worker/config.py``) with
one dataclass hierarchy resolved as: defaults <- config file (JSON/YAML) <-
environment variables <- explicit overrides.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Any, Optional

_ENV_PREFIX = "TPUML_"


@dataclasses.dataclass
class StorageConfig:
    """Filesystem layout. Mirrors the reference's /mnt/efs shared-volume layout
    (``aws-prod/master/config.py:11-12``). The default root is ``~/.tpuml``
    — outside the checkout; place it with ``TPUML_STORAGE__ROOT`` (datasets,
    journals, models, the native build and the AOT export cache all live
    under it; ``CS230_AOT_DIR`` moves the export cache alone)."""

    root: str = os.path.expanduser("~/.tpuml")

    @property
    def datasets_dir(self) -> str:
        return os.path.join(self.root, "datasets")

    @property
    def configs_dir(self) -> str:
        return os.path.join(self.root, "configs")

    @property
    def models_dir(self) -> str:
        return os.path.join(self.root, "models")

    @property
    def journal_dir(self) -> str:
        return os.path.join(self.root, "journal")

    @property
    def runtime_model_path(self) -> str:
        return os.path.join(self.root, "runtime_predictor.joblib")


@dataclasses.dataclass
class SchedulerConfig:
    """Placement-engine knobs. Values mirror the reference's operational
    constants (``worker.py:33``, ``scheduler_service.py:25,31,209-216``)."""

    heartbeat_interval_s: float = 5.0
    dead_after_s: float = 10.0
    sweep_interval_s: float = 15.0
    predictor_refit_batch: int = 10
    default_mem_capacity_mb: float = 16000.0
    speed_ema_alpha: float = 0.2
    speed_factor_min: float = 0.2
    speed_factor_max: float = 5.0
    algo_weights: dict = dataclasses.field(default_factory=dict)
    # ---- per-worker health telemetry (docs/OBSERVABILITY.md) ----
    # EWMA smoothing for a worker's batch wall time
    health_ema_alpha: float = 0.2
    # a worker is a straggler when its batch EWMA exceeds factor x the
    # median EWMA of its peers (each judged against the OTHERS' median, so
    # two-worker pools can flag too), after its EWMA has absorbed at least
    # min_batches BATCHES (outcomes arrive per subtask — counting them
    # would let one cold multi-subtask batch satisfy the guard)
    straggler_factor: float = 3.0
    straggler_min_batches: int = 2
    # advisory placement-score penalty (seconds) added to flagged
    # stragglers — eligibility and fallback semantics are untouched
    straggler_penalty_s: float = 30.0
    # ---- fault-tolerance layer (docs/ROBUSTNESS.md) ----
    # every placed subtask carries a lease: deadline = now +
    # max(lease_floor_s, lease_factor x predicted completion time on the
    # chosen worker, queue wait included). The sweep reclaims and requeues
    # expired leases from LIVE but hung workers. factor <= 0 disables.
    lease_factor: float = 4.0
    lease_floor_s: float = 30.0
    # total execution attempts per subtask before quarantine (failed or
    # lease-reclaimed executions both consume the budget)
    retry_max_attempts: int = 3
    # per-attempt exponential backoff before a failure retry:
    # retry_backoff_s x 2^(failures-1), capped at retry_backoff_max_s
    retry_backoff_s: float = 0.5
    retry_backoff_max_s: float = 10.0
    # a subtask that killed this many worker backends (DeviceLostError
    # correlation) is poisoned and quarantined immediately
    poison_kill_threshold: int = 2
    # speculative execution (MapReduce backup tasks): when a subtask's
    # in-flight time exceeds straggler_factor x the peer-median batch EWMA
    # (floored at speculative_min_inflight_s) and an idle worker exists,
    # launch ONE duplicate there; first terminal result wins
    speculative_enabled: bool = True
    speculative_min_inflight_s: float = 10.0
    # worker circuit breaker: trip to half-open (probe tasks only) when
    # failed/total outcomes since the last transition reaches the ratio
    # over at least min_outcomes; evict after max_trips trips. ratio <= 0
    # disables.
    breaker_failure_ratio: float = 0.5
    breaker_min_outcomes: int = 4
    breaker_max_trips: int = 3
    # ---- QoS lane aging (docs/ARCHITECTURE.md "QoS priority lanes") ----
    # strict-priority dispatch queues promote a waiting message one lane
    # per qos_aging_s seconds of queue age, so a sustained high-priority
    # flood cannot starve low lanes forever. <= 0 disables (pure strict
    # priority).
    qos_aging_s: float = 30.0


@dataclasses.dataclass
class ExecutionConfig:
    """Trial-execution knobs for the TPU compute path."""

    # mesh axis names
    trial_axis: str = "trials"
    data_axis: str = "data"
    # max trials fused into one vmapped super-batch per dispatch
    max_trials_per_batch: int = 256
    # default dtype for fitting kernels (MXU-friendly accumulate in f32)
    compute_dtype: str = "float32"
    # cv defaults matching sklearn cross_val_score(cv=5)
    default_cv_folds: int = 5
    default_test_size: float = 0.2


@dataclasses.dataclass
class ServiceConfig:
    """Control-plane endpoints (coordinator REST server + SSE cadence).
    SSE tick mirrors the reference's 1.5 s stream loop (``master.py:266``)."""

    host: str = "0.0.0.0"
    port: int = 5001
    sse_tick_s: float = 1.5
    client_poll_s: float = 1.0
    client_timeout_s: float = 600.0  # reference default of 60 s is too small
    # ---- admission control / overload survival (docs/ROBUSTNESS.md
    # "Coordinator recovery and overload survival") ----
    # hard caps on ACCEPTED work: a submit beyond any of them is rejected
    # with 429 + Retry-After instead of queueing the coordinator to death.
    # <= 0 disables the corresponding cap.
    max_inflight_jobs: int = 64
    max_inflight_jobs_per_session: int = 16
    # total PENDING subtasks across all unfinished jobs — the queue-depth
    # watermark (a 10-trial job and a 10k-trial job are not the same load)
    admission_queue_watermark: int = 50000
    # Retry-After seconds sent with 429 (admission) and 503 (recovering)
    admission_retry_after_s: float = 5.0
    # soft watermark: above this fraction of any enabled cap the engine
    # sheds optional work first (speculative duplicates, prewarm hints)
    # before admission starts rejecting
    shed_fraction: float = 0.8
    # client-side transport resilience: how long MLTaskManager keeps
    # retrying an idempotent request through 429/503/connection errors
    # (capped jittered backoff, Retry-After honored). 0 disables retries.
    request_retry_s: float = 60.0
    # ---- fleet health plane (docs/OBSERVABILITY.md "Fleet health
    # plane"): capacity signals (obs/signals.py) + SLO alert rules
    # (obs/slo.py) ----
    # evaluation floors: the engine sweep, /metrics/prom scrapes, and
    # /alerts //autoscale reads all drive evaluation — the throttle keeps
    # the drivers from multi-evaluating
    autoscale_interval_s: float = 5.0
    alert_eval_interval_s: float = 5.0
    # drain-time target: desired_workers is sized so the predictor-priced
    # backlog drains within this horizon (also the rejection-rate window
    # of the pressure probe)
    autoscale_horizon_s: float = 120.0
    autoscale_min_workers: int = 1
    autoscale_max_workers: int = 256
    # desired_shards targets this fill fraction of the admission caps
    autoscale_target_fill: float = 0.7
    # scale-down hysteresis: a below-live signal must hold this long (and
    # idle workers must exist to drain through the lease/evict path)
    # before the published gauge actually drops
    autoscale_downscale_hold_s: float = 180.0
    # SLO targets the default alert rules evaluate (obs/slo.py)
    route_p99_slo_s: float = 2.0
    sse_lag_slo_s: float = 5.0
    alert_admission_reject_per_s: float = 0.2
    # ---- cross-shard rebalancing (docs/ROBUSTNESS.md "Shard
    # rebalancing"): job migration + work stealing, driven by the
    # per-shard pressure signal (obs/signals.py tpuml_shard_pressure) ----
    # master valve: even with peers wired (server --peers) a shard takes
    # no rebalancing ACTION unless enabled (the peer endpoints still
    # answer, so a mixed fleet degrades to one-sided stealing)
    rebalance_enabled: bool = False
    # floor between rebalance passes (each pass does peer HTTP probes,
    # so it must not run at sweep/scrape cadence)
    rebalance_interval_s: float = 10.0
    # a shard at/above this tpuml_shard_pressure is HOT: it offers steal
    # candidates and looks for a cold peer to migrate a job to
    rebalance_hot_pressure: float = 2.0
    # a peer at/below this pressure is drainable-COLD: eligible migration
    # destination; a shard at/below it with idle workers turns thief
    rebalance_cold_pressure: float = 0.5
    # hot/cold pressure ratio floor: migration only fires when the skew
    # is real (keeps balanced fleets from ping-ponging jobs)
    rebalance_imbalance_ratio: float = 3.0
    # how long the donor keeps replaying-forward late results for a
    # migrated job (at-least-once across the handoff)
    rebalance_forward_s: float = 120.0
    # max queued subtasks one steal grant hands a thief shard
    steal_max_tasks: int = 8
    # donor-side steal lease: a tombstone older than this with no result
    # from the thief is reclaimed (fresh attempt fences the thief)
    steal_lease_s: float = 120.0
    # ---- trial telemetry plane (docs/OBSERVABILITY.md "Trial telemetry
    # plane"): numerical-health watchdog threshold. A trial whose curve
    # tail (loss or grad-norm) exceeds this factor x the median of its
    # own early trace — or contains any non-finite sample — is marked
    # diverged and its in-flight attempt is cooperatively cancelled.
    # <= 0 disables the ratio rule (non-finite still trips).
    curve_divergence_factor: float = 1e3


@dataclasses.dataclass
class FrameworkConfig:
    storage: StorageConfig = dataclasses.field(default_factory=StorageConfig)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    execution: ExecutionConfig = dataclasses.field(default_factory=ExecutionConfig)
    service: ServiceConfig = dataclasses.field(default_factory=ServiceConfig)

    @classmethod
    def load(
        cls,
        path: Optional[str] = None,
        env: Optional[dict] = None,
        **overrides: Any,
    ) -> "FrameworkConfig":
        cfg = cls()
        if path:
            cfg = cfg.merged(_read_config_file(path))
        cfg = cfg.merged(_env_overrides(env if env is not None else os.environ))
        if overrides:
            cfg = cfg.merged(overrides)
        return cfg

    def merged(self, updates: dict) -> "FrameworkConfig":
        return _merge_dataclass(self, updates)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _merge_dataclass(obj, updates: dict):
    if not dataclasses.is_dataclass(obj):
        return updates
    kwargs = {}
    for f in dataclasses.fields(obj):
        cur = getattr(obj, f.name)
        if f.name in updates:
            upd = updates[f.name]
            if dataclasses.is_dataclass(cur) and isinstance(upd, dict):
                kwargs[f.name] = _merge_dataclass(cur, upd)
            else:
                kwargs[f.name] = upd
        else:
            kwargs[f.name] = cur
    return type(obj)(**kwargs)


def _read_config_file(path: str) -> dict:
    text = Path(path).read_text()
    if path.endswith((".yaml", ".yml")):
        import yaml

        return yaml.safe_load(text) or {}
    return json.loads(text)


def _env_overrides(env) -> dict:
    """TPUML_SECTION__FIELD=value -> {"section": {"field": parsed}}."""
    out: dict = {}
    for key, raw in env.items():
        if not key.startswith(_ENV_PREFIX):
            continue
        parts = key[len(_ENV_PREFIX):].lower().split("__")
        if len(parts) != 2:
            continue
        section, field = parts
        try:
            value: Any = json.loads(raw)
        except (json.JSONDecodeError, ValueError):
            value = raw
        out.setdefault(section, {})[field] = value
    return out


_GLOBAL_CONFIG: Optional[FrameworkConfig] = None


def get_config() -> FrameworkConfig:
    global _GLOBAL_CONFIG
    if _GLOBAL_CONFIG is None:
        _GLOBAL_CONFIG = FrameworkConfig.load()
    return _GLOBAL_CONFIG


def set_config(cfg: FrameworkConfig) -> None:
    global _GLOBAL_CONFIG
    _GLOBAL_CONFIG = cfg
