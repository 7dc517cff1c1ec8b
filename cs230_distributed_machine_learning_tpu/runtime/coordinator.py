"""Coordinator: sessions, job fan-out, result collection, aggregation.

The TPU-native replacement for the reference master + its Redis/Kafka glue
(``aws-prod/master/master.py``, ``task_handler.py``): one process owning the
job store, the topic bus, and the executor pool. The job lifecycle mirrors
the reference exactly — create session, stage dataset, preprocess, expand a
train job into per-trial subtasks, dispatch, collect results, aggregate by
``mean_cv_score`` (``task_handler.py:254-263``) — minus the brokers: fan-out
is an in-process dispatch to the mesh executor, results flow back through
callbacks + the bus, progress is a store read instead of a Redis poll.
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from ..data.datasets import DatasetCache
from ..data.download import download_dataset
from ..data.preprocess import preprocess_dataframe
from ..obs import (
    RECORDER,
    TRACER,
    activate,
    counter_inc,
    current_trace_id,
    gauge_set,
    new_trace_id,
    record_event,
    span,
)
from ..obs.curves import CurveStore, divergence
from ..utils.config import FrameworkConfig, get_config
from ..utils.logging import get_logger
from ..utils.serialization import json_safe
from .artifacts import save_artifact
from .executor import LocalExecutor
from .queue import TopicBus
from .search import SearchJobDriver, Step
from .store import SUBTASK_TERMINAL_STATUSES, TERMINAL_STATUSES, JobStore
from .subtasks import create_subtasks

logger = get_logger("tpuml.coordinator")

TOPIC_RESULTS = "result"
TOPIC_METRICS = "metrics"


class JobMigratedError(Exception):
    """Raised inside a job's ingest loop when the rebalancer has marked
    the job for migration: the loop unwinds WITHOUT finalizing (the
    destination shard owns completion now) and without the generic
    failure path (nothing failed — the job moved)."""


class Coordinator:
    def __init__(
        self,
        config: Optional[FrameworkConfig] = None,
        *,
        mesh=None,
        executor: Optional[LocalExecutor] = None,
        cluster=None,
        journal: bool = False,
        journal_dir: Optional[str] = None,
        shard_id: Optional[int] = None,
        n_shards: int = 1,
    ):
        """Two dispatch modes: direct (default — one in-process executor, the
        single-host TPU deployment) and scheduled (``cluster=`` a
        ClusterRuntime — placement-engine dispatch over an executor pool
        with heartbeats/requeue, the reference's full topology).

        ``shard_id``/``n_shards`` make this coordinator ONE shard of a
        sharded control plane (docs/ARCHITECTURE.md "Sharded control
        plane"): job ids are stamped ``s<shard>-`` so any stateless front
        end routes them, and ``journal_dir`` points at this shard's OWN
        journal (``<journal>/shard-<k>``) — the unit of hot-standby
        takeover (a replacement process replaying it finishes the dead
        shard's jobs, docs/ROBUSTNESS.md "Shard takeover")."""
        self.config = config or get_config()
        self.cluster = cluster
        self.shard_id = shard_id
        self.n_shards = max(int(n_shards), 1)
        self.bus = cluster.bus if cluster is not None else TopicBus()
        self.store = JobStore(
            journal_dir=(
                (journal_dir or self.config.storage.journal_dir)
                if journal else None
            )
        )
        self.cache = (
            cluster.cache
            if cluster is not None and cluster.cache is not None
            else DatasetCache(root=self.config.storage.datasets_dir)
        )
        if cluster is not None and cluster.cache is None:
            cluster.cache = self.cache
        # retained in cluster mode too: artifact refits run coordinator-side
        self.executor = executor or LocalExecutor(mesh=mesh, cache=self.cache)
        self._job_threads: Dict[str, threading.Thread] = {}
        self._artifact_lock = threading.Lock()
        self._artifact_paths: Dict[Any, str] = {}
        self._artifact_specs: Dict[Any, Dict[str, Any]] = {}
        #: submit-dedupe guard: job_ids currently being expanded, so a
        #: retried duplicate POST arriving DURING expansion (the store
        #: doesn't know the job yet) can't double-expand
        self._submit_lock = threading.Lock()
        self._submitting: set = set()
        #: readiness (GET /readyz): False while the journal is being
        #: replayed / in-flight jobs re-queued, so load balancers and the
        #: chaos harness can gate on recovery completion
        self.ready = not journal
        #: recovery forensics for /healthz (replayed-op counts, wall time)
        self.recovery: Dict[str, Any] = {}
        # fleet health plane (docs/OBSERVABILITY.md "Fleet health
        # plane"): capacity-signal deriver (GET /autoscale) + SLO alert
        # rules engine (GET /alerts), evaluated on the engine sweep in
        # cluster mode and at scrape//read time in direct mode
        from ..obs.signals import CapacitySignals
        from ..obs.slo import AlertEngine, default_rules

        # trial telemetry plane (docs/OBSERVABILITY.md "Trial telemetry
        # plane"): bounded in-memory store of per-trial learning curves —
        # fed by result/metrics ingest, read by GET /curves and the SSE
        # stream, consulted by the numerical-health watchdog
        self.curves = CurveStore()
        self.signals = CapacitySignals(self)
        self.alerts = AlertEngine(
            default_rules(self.config),
            interval_s=self.config.service.alert_eval_interval_s,
        )
        #: peer shard base URLs, index == shard id (server --peers /
        #: ShardFleet). Empty on unsharded deployments — every
        #: rebalancing path below is inert without peers.
        self.peer_urls: List[str] = []
        #: jobs being quiesced for migration: (sid, jid) -> dest shard.
        #: The scheduled ingest loop checks this each iteration and
        #: unwinds via JobMigratedError — the quiesce half of the
        #: migration state machine (docs/ROBUSTNESS.md).
        self._migrating: Dict[tuple, int] = {}
        self._rebalance_lock = threading.Lock()
        self._rebalance_busy = False
        self._last_rebalance = 0.0
        if cluster is not None:
            # journal every attempt issue (lease reclaim / retry / requeue /
            # speculation) into the job store so replay preserves budgets,
            # and every placement/lease grant so a restarted coordinator
            # can tell dispatched in-flight subtasks from never-dispatched
            # ones (docs/ROBUSTNESS.md "Coordinator recovery")
            cluster.ledger.on_attempt = self._journal_attempt
            cluster.engine.on_place = self._journal_placement
            # journal mesh-generation bumps (worker join/death/evict) so
            # a recovered coordinator replays the fleet's reshard history
            # (docs/ARCHITECTURE.md "Elastic trial fabric")
            cluster.engine.on_mesh_change = self._journal_mesh_change
            # overload probe: speculation sheds first under load
            cluster.engine.shed_check = self.overload_shedding
            cluster.engine.on_sweep_end = self.health_tick
        if journal:
            self._recover()

    def health_tick(self, force: bool = False) -> None:
        """One fleet-health evaluation: derive the capacity signals and
        run the alert rules. Driven by the engine sweep (cluster mode),
        every ``/metrics/prom`` scrape, and ``/alerts`` / ``/autoscale``
        reads (direct-mode coordinators have no sweep) — both halves are
        internally throttled so the drivers don't multi-evaluate."""
        try:
            self.signals.evaluate(force=force)
        except Exception:  # noqa: BLE001 — health derivation must never break a caller
            logger.exception("Capacity-signal derivation failed")
        try:
            self.alerts.evaluate(force=force)
        except Exception:  # noqa: BLE001
            logger.exception("Alert-rule evaluation failed")
        try:
            self.rebalance_tick()
        except Exception:  # noqa: BLE001 — rebalancing must never break a caller
            logger.exception("Rebalance tick failed")

    def _recover(self) -> None:
        """Boot-time crash recovery: surface the journal replay the store
        already ran, re-queue in-flight work, and flip readiness. The
        whole sequence is synchronous — a coordinator is never serving
        while half-recovered."""
        t0 = time.time()
        for op, n in self.store.replay_ops.items():
            counter_inc("tpuml_recovery_replayed_ops_total", n, op=op)
        record_event(
            "recovery.start",
            replayed_ops=sum(self.store.replay_ops.values()),
            replay_skipped=self.store.replay_skipped,
            replay_seconds=round(self.store.replay_seconds, 6),
        )
        if self.cluster is not None and self.store.mesh_generation:
            # resume the reshard counter monotonically: workers that
            # registered before recovery finished already bumped the live
            # engine, so take the max of both histories — and refresh the
            # gauges, which otherwise keep the pre-recovery value until
            # the next live reshard
            eng = self.cluster.engine
            with eng._lock:  # merge under the bump lock: a concurrent
                # join's increment must not be overwritten
                eng.mesh_generation = max(
                    eng.mesh_generation, self.store.mesh_generation
                )
                gauge_set(
                    "tpuml_mesh_generation", float(eng.mesh_generation)
                )
                gauge_set(
                    "tpuml_mesh_devices_total", float(eng.total_devices())
                )
        # re-seed the curve store from journaled ``curve`` ops: rung-
        # boundary curves survive a restart, so /curves and the watchdog's
        # divergence history pick up where the dead coordinator left off
        replayed_curves = self.store.drain_replayed_curves()
        for e in replayed_curves:
            self.curves.ingest(
                e["jid"], e["stid"], e["curve"],
                rung=e.get("rung", 0), attempt=e.get("attempt", 0),
                diverged=bool(e.get("diverged")),
            )
        resumed = self.resume_inflight()
        recovery_s = self.store.replay_seconds + (time.time() - t0)
        self.recovery = {
            "replayed_ops": dict(self.store.replay_ops),
            "replay_skipped": self.store.replay_skipped,
            "jobs_resumed": len(resumed),
            "subtasks_requeued": self._resume_requeued,
            "curves_replayed": len(replayed_curves),
            "recovery_seconds": recovery_s,
        }
        gauge_set("tpuml_coordinator_recovery_seconds", recovery_s)
        record_event("recovery.done", **self.recovery)
        if resumed:
            logger.info(
                "Recovery done in %.3fs: %d ops replayed, %d jobs resumed, "
                "%d subtasks re-queued",
                recovery_s, sum(self.store.replay_ops.values()),
                len(resumed), self._resume_requeued,
            )
        self.ready = True

    def _journal_attempt(self, task: Dict[str, Any], entry, reason: str) -> None:
        sid = task.get("session_id")
        jid = task.get("job_id")
        stid = task.get("subtask_id")
        if not (sid and jid and stid):
            return
        try:
            self.store.record_attempt(
                sid, jid, stid,
                attempt=entry.attempt,
                failures=entry.failures,
                excluded=entry.excluded,
            )
        except KeyError:
            # a job this store never saw (foreign traffic on a shared
            # cluster): nothing to journal
            pass

    def _journal_mesh_change(
        self, generation: int, reason: str, snapshot: Dict[str, Any]
    ) -> None:
        try:
            self.store.record_mesh_generation(generation, reason)
        except Exception:  # noqa: BLE001 — journaling must not block resharding
            logger.exception("Mesh-generation journal failed")

    def _journal_placement(self, task: Dict[str, Any], worker_id: str,
                           lease_deadline=None) -> None:
        sid = task.get("session_id")
        jid = task.get("job_id")
        stid = task.get("subtask_id")
        if not (sid and jid and stid):
            return
        try:
            self.store.record_placement(
                sid, jid, stid, worker_id,
                attempt=int(task.get("attempt") or 0),
                lease_deadline=lease_deadline,
            )
        except KeyError:
            pass  # foreign traffic on a shared cluster: nothing to journal

    #: subtasks re-dispatched by the most recent resume_inflight()
    _resume_requeued = 0

    def resume_inflight(self) -> List[str]:
        """Re-dispatch jobs the journal shows as unfinished: replay restores
        state, this restores WORK — a coordinator killed mid-job completes it
        after restart without client resubmission (beyond the reference,
        whose master restart loses in-flight jobs; Redis AOF only kept
        state, SURVEY.md §5.4). Subtasks with a journaled terminal result
        are not re-run. In cluster mode, subtasks the journal shows as
        PLACED pre-crash get a fresh attempt id before re-queueing: a
        zombie worker's late FAILED report then carries a superseded stamp
        and cannot burn retry budget, while its late COMPLETED report is
        still accepted (first terminal result wins — the at-least-once
        re-ingest contract, docs/ROBUSTNESS.md)."""
        resumed = []
        self._resume_requeued = 0
        for sid, job_id in self.store.unfinished_jobs():
            job = self.store.get_job(sid, job_id)
            specs = [sub["spec"] for sub in job["subtasks"].values()]
            existing = {
                stid: sub["result"]
                for stid, sub in job["subtasks"].items()
                if sub["status"] in SUBTASK_TERMINAL_STATUSES
                and sub["result"]
            }
            remaining = [
                st for st in specs if st["subtask_id"] not in existing
            ]
            if self.cluster is not None:
                for st in remaining:
                    if st.get("placed_worker") is None:
                        continue  # never dispatched (or pre-place journal)
                    self.cluster.ledger.seed(st)
                    self.cluster.ledger.next_attempt(st, reason="recovery")
            logger.info(
                "Resuming job %s: %d/%d subtasks already journaled",
                job_id, len(existing), len(specs),
            )
            record_event(
                "job.resume", job_id=job_id,
                n_done=len(existing), n_requeued=len(remaining),
            )
            counter_inc("tpuml_recovery_jobs_resumed_total")
            counter_inc(
                "tpuml_recovery_subtasks_requeued_total", len(remaining)
            )
            self._resume_requeued += len(remaining)
            t = threading.Thread(
                target=self._run_job,
                args=(sid, job_id, specs),
                kwargs={"existing": existing},
                daemon=True,
            )
            self._job_threads[job_id] = t
            t.start()
            resumed.append(job_id)
        return resumed

    # ------------- cross-shard rebalancing (docs/ROBUSTNESS.md "Shard rebalancing") -------------
    # The fleet acting on its own telemetry: a HOT shard (high
    # tpuml_shard_pressure) migrates whole jobs to a drainable-COLD peer
    # and offers queued subtasks to thieves; an idle shard steals. Both
    # paths ride the existing crash-safety machinery — journal ops with
    # total replay, attempt-stamp fencing, first-terminal-result-wins
    # dedup — so a SIGKILL of either party at any phase loses nothing.

    def rebalance_tick(self) -> None:
        """Throttled entry point, driven by health_tick (engine sweep /
        scrapes). The actual pass runs on a background thread — it makes
        peer HTTP probes and must never stall a sweep."""
        svc = self.config.service
        if (
            not svc.rebalance_enabled
            or self.cluster is None
            or self.shard_id is None
            or not self.peer_urls
            or not self.ready
        ):
            return
        now = time.time()
        with self._rebalance_lock:
            if (
                self._rebalance_busy
                or now - self._last_rebalance < svc.rebalance_interval_s
            ):
                return
            self._rebalance_busy = True
            self._last_rebalance = now
        threading.Thread(target=self._rebalance_once, daemon=True).start()

    def _rebalance_once(self) -> None:
        try:
            self._reclaim_stale_steals()
            rep = self.signals.evaluate()
            sig = rep.get("signals") or {}
            my_p = float(sig.get("shard_pressure") or 0.0)
            svc = self.config.service
            if my_p >= svc.rebalance_hot_pressure:
                self._migrate_if_peer_cold(my_p)
            elif (
                my_p <= svc.rebalance_cold_pressure
                and int(sig.get("idle_workers") or 0) > 0
            ):
                self._steal_from_hot_peer()
        except Exception:  # noqa: BLE001 — a failed pass must not wedge the next
            logger.exception("Rebalance pass failed")
        finally:
            with self._rebalance_lock:
                self._rebalance_busy = False

    def _peer_pressures(self) -> Dict[int, float]:
        """shard_pressure of every answering peer (short timeouts — a
        dead peer is simply not a candidate)."""
        import requests

        out: Dict[int, float] = {}
        for k, url in enumerate(self.peer_urls):
            if k == self.shard_id or not url:
                continue
            try:
                r = requests.get(f"{url}/autoscale", timeout=3)
                if r.ok:
                    sig = (r.json() or {}).get("signals") or {}
                    out[k] = float(sig.get("shard_pressure") or 0.0)
            except (requests.RequestException, ValueError):
                continue
        return out

    def _migrate_if_peer_cold(self, my_pressure: float) -> None:
        svc = self.config.service
        peers = self._peer_pressures()
        if not peers:
            return
        dest, cold = min(peers.items(), key=lambda kv: kv[1])
        if cold > svc.rebalance_cold_pressure:
            return
        if cold > 0 and my_pressure / cold < svc.rebalance_imbalance_ratio:
            return  # hot, but not hot ENOUGH relative to the peer
        picked = self._pick_migratable()
        if picked is None:
            return
        sid, jid = picked
        self.migrate_job(sid, jid, dest)

    def _pick_migratable(self) -> Optional[tuple]:
        """Cheapest unfinished job that can move: not mid-expansion, not
        already migrating, and not an adaptive-search job (the rung
        controller's in-memory ladder state has no export contract — a
        migrated ASHA job would restart its schedule from the journaled
        rung history on the WRONG shard's recorder; excluded by design,
        documented in docs/ROBUSTNESS.md). Among the eligible, a job with
        nothing currently EXECUTING (no subtask at the head of a worker
        queue — the same queued-vs-running line the steal offer draws)
        wins: quiescing it fences only queued attempts and throws away no
        in-flight work. A job mid-execution is the fallback, not the
        first pick."""
        heads = set()
        if self.cluster is not None:
            for q in self.cluster.engine.queue_snapshot().values():
                if q:
                    heads.add(q[0])
        fallback: Optional[tuple] = None
        for sid, jid in self.store.unfinished_jobs():
            if (sid, jid) in self._migrating:
                continue
            with self._submit_lock:
                if jid in self._submitting:
                    continue
            try:
                job = self.store.get_job(sid, jid)
            except KeyError:
                continue
            subs = job.get("subtasks") or {}
            if any((s.get("spec") or {}).get("asha") for s in subs.values()):
                continue
            # anti-ping-pong: a job migrates at most once. Re-exporting
            # an adopted job would let two shards trade the same job
            # every tick while both hover near the hot threshold.
            if job.get("migrated_from") is not None:
                continue
            live = [
                stid for stid, s in subs.items()
                if s["status"] not in SUBTASK_TERMINAL_STATUSES
            ]
            if not live:
                continue
            if not any(stid in heads for stid in live):
                return sid, jid
            if fallback is None:
                fallback = (sid, jid)
        return fallback

    def migrate_job(self, sid: str, job_id: str, dest_shard: int) -> bool:
        """Donor half of the migration state machine:

        1. **quiesce** — mark the job migrating; its ingest loop unwinds
           (JobMigratedError) without finalizing.
        2. **fence** — bump every non-terminal subtask's attempt
           (journaled via the on_attempt hook) and release its engine
           book entry: no donor-side copy can re-dispatch, and any
           still-executing worker's late FAILED report is stale by
           construction (its COMPLETED is still accepted — at-least-once).
        3. **export** — POST the full job record to the peer's
           ``/migrate_in``; the RECIPIENT journals ``migrate_in`` first.
        4. **stamp** — only after the peer accepted, journal
           ``migrate_out`` (the forwarding stamp). Crash between 3 and 4
           leaves BOTH shards owning the job: clients still route to the
           donor (no stamp), so results stay consistent and the
           recipient's copy is wasted work deduped by first-wins — never
           a lost job. Crash before 3 (or a failed POST) aborts and the
           job respawns locally.
        5. **forward** — replay-forward late donor-side results to the
           new owner for ``rebalance_forward_s``.
        """
        import os as _os

        import requests

        if self.cluster is None or not self.peer_urls:
            return False
        try:
            url = self.peer_urls[int(dest_shard)]
        except (IndexError, ValueError):
            return False
        record_event(
            "migrate.start", job_id=job_id, dest_shard=int(dest_shard),
        )
        self._migrating[(sid, job_id)] = int(dest_shard)
        try:
            t = self._job_threads.get(job_id)
            if t is not None and t.is_alive():
                t.join(timeout=30.0)
                if t.is_alive():
                    record_event(
                        "migrate.abort", job_id=job_id,
                        dest_shard=int(dest_shard),
                        reason="quiesce_timeout",
                    )
                    return False  # loop never unwound: leave the job alone
            # ---- fence ----
            job = self.store.get_job(sid, job_id)
            owner = {
                stid: wid
                for wid, q in self.cluster.engine.queue_snapshot().items()
                for stid in q
            }
            fenced = 0
            for stid, sub in job["subtasks"].items():
                if sub["status"] in SUBTASK_TERMINAL_STATUSES:
                    continue
                task = dict(sub["spec"])
                self.cluster.ledger.seed(task)
                self.cluster.ledger.next_attempt(task, reason="migrate")
                wid = owner.get(stid) or task.get("placed_worker")
                if wid:
                    self.cluster.engine.release_task(wid, stid)
                self.store.clear_steal(stid)
                fenced += 1
            # ---- export (re-read: the fence journaled fresh attempts
            # into the specs, and the recipient must adopt THOSE) ----
            job = self.store.get_job(sid, job_id)
            export = {
                "session_id": sid,
                "priority": self.store.session_priority(sid),
                "source_shard": self.shard_id,
                "job": job,
            }
            try:
                r = requests.post(
                    f"{url}/migrate_in", json=json_safe(export), timeout=30
                )
            except requests.RequestException as e:
                self._abort_migration(sid, job_id, f"peer_unreachable: {e}")
                return False
            if r.status_code != 200:
                self._abort_migration(
                    sid, job_id, f"peer_rejected: HTTP {r.status_code}"
                )
                return False
            # chaos-drill hook: hold the riskiest window (recipient has
            # the job, donor not yet stamped) open so the harness can
            # land a deterministic SIGKILL inside it
            delay = float(_os.environ.get("CS230_MIGRATE_DELAY_S", 0) or 0)
            if delay > 0:
                time.sleep(delay)
            # ---- stamp ----
            self.store.record_migrate_out(sid, job_id, int(dest_shard))
            counter_inc("tpuml_jobs_migrated_total", direction="out")
            record_event(
                "migrate.out", job_id=job_id, dest_shard=int(dest_shard),
                n_fenced=fenced,
            )
            logger.info(
                "Migrated job %s to shard %d (%d subtasks fenced)",
                job_id, int(dest_shard), fenced,
            )
            # ---- forward late results ----
            pending = [
                stid for stid, sub in job["subtasks"].items()
                if sub["status"] not in SUBTASK_TERMINAL_STATUSES
            ]
            self._forward_late_results(job_id, int(dest_shard), pending)
            self.cluster.ledger.forget(list(job["subtasks"]))
            return True
        finally:
            self._migrating.pop((sid, job_id), None)

    def _abort_migration(self, sid: str, job_id: str, reason: str) -> None:
        """Failed export: the job never left. Clear the quiesce mark and
        respawn it locally — the fenced attempts simply re-dispatch here
        (same recovery semantics as a restart)."""
        record_event("migrate.abort", job_id=job_id, reason=reason)
        logger.warning("Migration of job %s aborted: %s", job_id, reason)
        self._migrating.pop((sid, job_id), None)
        self._respawn_job(sid, job_id)

    def _respawn_job(self, sid: str, job_id: str) -> None:
        """Resume ONE job from its store record (the per-job slice of
        resume_inflight): dispatch what isn't terminal, keep what is."""
        job = self.store.get_job(sid, job_id)
        specs = [sub["spec"] for sub in job["subtasks"].values()]
        existing = {
            stid: sub["result"]
            for stid, sub in job["subtasks"].items()
            if sub["status"] in SUBTASK_TERMINAL_STATUSES and sub["result"]
        }
        t = threading.Thread(
            target=self._run_job,
            args=(sid, job_id, specs),
            kwargs={"existing": existing},
            daemon=True,
        )
        self._job_threads[job_id] = t
        t.start()

    def _forward_late_results(
        self, job_id: str, dest_shard: int, pending_ids: List[str]
    ) -> None:
        """Donor-side replay-forward: results for a migrated job's
        still-open subtasks (zombie workers finishing fenced attempts)
        are POSTed to the new owner's ``/peer_result`` for a bounded
        window, so the at-least-once ingest contract survives the
        handoff — the recipient's first-wins dedup absorbs any overlap
        with its own re-dispatched attempts."""
        if not pending_ids:
            return
        import queue as _q

        import requests

        url = self.peer_urls[dest_shard]
        wanted = set(pending_ids)
        sub = self.bus.subscribe(
            TOPIC_RESULTS, key_filter=lambda k: k in wanted
        )
        deadline = time.time() + self.config.service.rebalance_forward_s

        def _pump():
            # one successful relay per subtask: duplicate reports (a
            # worker re-sending, or the recipient echoing a stolen
            # result we already forwarded) must not re-post, or a
            # migrated-after-steal subtask ping-pongs between the two
            # shards until both relay deadlines expire
            done: set = set()
            try:
                while time.time() < deadline and len(done) < len(wanted):
                    try:
                        stid, result = sub.get(timeout=1.0)
                    except _q.Empty:
                        continue
                    if stid in done:
                        continue
                    try:
                        requests.post(
                            f"{url}/peer_result",
                            json=json_safe(result or {}),
                            timeout=10,
                        )
                        done.add(stid)
                        counter_inc("tpuml_results_forwarded_total")
                        record_event(
                            "migrate.forward", job_id=job_id,
                            subtask_id=stid, dest_shard=dest_shard,
                        )
                    except requests.RequestException:
                        logger.warning(
                            "Forwarding late result %s to shard %d failed",
                            stid, dest_shard,
                        )
            finally:
                sub.close()

        threading.Thread(target=_pump, daemon=True).start()

    def migrate_in(self, export: Dict[str, Any]) -> Dict[str, Any]:
        """Recipient half: journal the adopted record (``migrate_in`` —
        BEFORE the donor stamps ``migrate_out``, so no crash ordering
        loses the job), then resume it like a recovered local job. A
        duplicate POST (donor retry) is answered idempotently."""
        if self.cluster is None:
            raise ValueError(
                "job migration requires a clustered coordinator"
            )
        job = (export or {}).get("job") or {}
        sid = (export or {}).get("session_id")
        job_id = job.get("job_id")
        if not (sid and job_id and job.get("subtasks") is not None):
            raise ValueError("malformed migration export")
        if self.store.has_job(sid, job_id):
            return {
                "status": "accepted", "job_id": job_id,
                "shard": self.shard_id, "duplicate": True,
            }
        src = export.get("source_shard")
        self.store.create_session(
            sid, priority=int(export.get("priority") or 0)
        )
        self.store.import_job(sid, job, source_shard=src)
        counter_inc("tpuml_jobs_migrated_total", direction="in")
        record_event(
            "migrate.in", job_id=job_id, source_shard=src,
            n_subtasks=len(job.get("subtasks") or {}),
        )
        logger.info(
            "Adopted job %s from shard %s (%d subtasks)",
            job_id, src, len(job.get("subtasks") or {}),
        )
        self._respawn_job(sid, job_id)
        return {
            "status": "accepted", "job_id": job_id, "shard": self.shard_id,
        }

    # ---- work stealing ----

    def steal_candidates(self) -> Dict[str, Any]:
        """Donor surface (``GET /steal_candidates``): queued, steal-
        eligible subtasks an idle peer may pull — offered only while this
        shard is HOT (a balanced fleet advertises nothing). Per-worker
        queue heads are withheld (likely already executing), as are
        tombstoned (already-granted) and adaptive-search subtasks."""
        out: Dict[str, Any] = {
            "shard": self.shard_id,
            "candidates": [],
            "shard_pressure": None,
            "backlog_device_seconds": None,
        }
        if self.cluster is None or not self.config.service.rebalance_enabled:
            return out
        sig = (self.signals.report() or {}).get("signals") or {}
        out["shard_pressure"] = sig.get("shard_pressure")
        out["backlog_device_seconds"] = sig.get("backlog_device_seconds")
        if (
            float(sig.get("shard_pressure") or 0.0)
            < self.config.service.rebalance_hot_pressure
        ):
            return out
        tomb = dict(self.store.steal_tombstones)
        snap = self.cluster.engine.worker_snapshot()
        owner = {
            stid: wid
            for wid, q in self.cluster.engine.queue_snapshot().items()
            for stid in q[1:]
            if stid not in tomb
        }
        info = self.store.lookup_specs(list(owner))
        for stid, rec in info.items():
            spec = rec["spec"]
            if spec.get("asha"):
                continue
            out["candidates"].append(
                {
                    "subtask_id": stid,
                    "job_id": rec["job_id"],
                    "session_id": rec["session_id"],
                    "est_s": spec.get("est_s"),
                    # priced width: the mesh slice the donor's engine
                    # packed this trial onto — a thief filters candidates
                    # to what its own widest IDLE slice can serve
                    # (heterogeneous fleets must not pull 8-device work
                    # onto a 1-device shard)
                    "n_devices": int(
                        (snap.get(owner[stid]) or {}).get("n_devices") or 1
                    ),
                }
            )
        return out

    def release_for_steal(
        self, thief_shard: int, max_n: int,
        max_n_devices: Optional[int] = None, prefer_wide: bool = False,
    ) -> List[Dict[str, Any]]:
        """Donor grant (``POST /steal_tasks``): hand up to ``max_n``
        queued subtasks to a thief shard as FRESH ledger attempts. Each
        grant bumps the attempt (fencing the queued donor copy — its
        late FAILED is stale, its late COMPLETED still wins first),
        releases the engine book entry, and journals a ``steal``
        tombstone so neither a live nor a restarted donor re-dispatches
        the subtask inside the steal lease.

        Mesh-aware grants: ``max_n_devices`` (the thief's widest idle
        slice) filters out candidates priced wider than the thief can
        serve; ``prefer_wide`` grants the widest-priced candidates first
        so wide trials land on wide slices. Both default to the legacy
        width-blind behavior for old thieves."""
        if (
            self.cluster is None
            or not self.config.service.rebalance_enabled
            or max_n <= 0
        ):
            return []
        tomb = dict(self.store.steal_tombstones)
        snap = self.cluster.engine.worker_snapshot()
        owner = {
            stid: wid
            for wid, q in self.cluster.engine.queue_snapshot().items()
            for stid in q[1:]
            if stid not in tomb
        }
        width = {
            stid: int((snap.get(wid) or {}).get("n_devices") or 1)
            for stid, wid in owner.items()
        }
        if max_n_devices is not None:
            owner = {
                stid: wid for stid, wid in owner.items()
                if width[stid] <= int(max_n_devices)
            }
        info = self.store.lookup_specs(list(owner))
        items = sorted(
            info.items(),
            key=(
                (lambda kv: (-width.get(kv[0], 1), kv[0]))
                if prefer_wide else (lambda kv: kv[0])
            ),
        )
        granted: List[Dict[str, Any]] = []
        for stid, rec in items:
            if len(granted) >= int(max_n):
                break
            if rec["spec"].get("asha"):
                continue
            task = dict(rec["spec"])
            self.cluster.ledger.seed(task)
            self.cluster.ledger.next_attempt(task, reason="steal")
            self.cluster.engine.release_task(owner[stid], stid)
            self.store.record_steal(
                rec["session_id"], rec["job_id"], stid,
                thief_shard=int(thief_shard),
                attempt=int(task.get("attempt") or 0),
            )
            task["metadata"] = rec["metadata"]
            task["stolen_from"] = self.shard_id
            granted.append(task)
            counter_inc("tpuml_subtasks_stolen_total", direction="out")
            record_event(
                "steal.out", job_id=rec["job_id"], subtask_id=stid,
                attempt=int(task.get("attempt") or 0),
                thief_shard=int(thief_shard),
                n_devices=width.get(stid, 1),
            )
        if granted:
            logger.info(
                "Granted %d queued subtasks to thief shard %d",
                len(granted), int(thief_shard),
            )
        return granted

    def _steal_from_hot_peer(self) -> None:
        """Thief half: poll peers' ``/steal_candidates``, pull from the
        hottest offering shard, run the grants on the local fabric, and
        relay every result back to the donor's ``/peer_result`` (the
        donor's still-running ingest loop counts them — its ledger
        expects exactly the granted attempt).

        Mesh-aware: candidates are priced with the device width of the
        slice the donor packed them onto, and this thief only pulls work
        its widest IDLE slice can serve — preferring the widest-priced
        candidates so wide trials land on wide slices instead of
        serializing on whatever narrow worker is free."""
        import requests

        svc = self.config.service
        # widest idle local slice: the upper bound on the candidate width
        # this shard can usefully absorb right now
        widest_idle = 0
        try:
            snap = self.cluster.engine.worker_snapshot()
            for wid, q in self.cluster.engine.queue_snapshot().items():
                if not q:
                    widest_idle = max(
                        widest_idle,
                        int((snap.get(wid) or {}).get("n_devices") or 1),
                    )
        except Exception:  # noqa: BLE001 — a torn snapshot must not crash the sweep
            widest_idle = 0
        if widest_idle <= 0:
            return  # no idle slice: stolen work would only queue here
        offers: Dict[int, Dict[str, Any]] = {}
        for k, url in enumerate(self.peer_urls):
            if k == self.shard_id or not url:
                continue
            try:
                r = requests.get(f"{url}/steal_candidates", timeout=3)
                if r.ok:
                    body = r.json() or {}
                    servable = [
                        c for c in (body.get("candidates") or [])
                        if int(c.get("n_devices") or 1) <= widest_idle
                    ]
                    if servable:
                        body["candidates"] = servable
                        offers[k] = body
            except (requests.RequestException, ValueError):
                continue
        if not offers:
            return
        donor = max(
            offers,
            key=lambda k: float(offers[k].get("shard_pressure") or 0.0),
        )
        try:
            r = requests.post(
                f"{self.peer_urls[donor]}/steal_tasks",
                json={
                    "thief_shard": self.shard_id,
                    "max_n": int(svc.steal_max_tasks),
                    "max_n_devices": widest_idle,
                    "prefer_wide": widest_idle > 1,
                },
                timeout=10,
            )
        except requests.RequestException:
            return
        if not r.ok:
            return
        try:
            tasks = (r.json() or {}).get("tasks") or []
        except ValueError:
            return
        if tasks:
            self._run_stolen(donor, tasks)

    def _run_stolen(
        self, donor_shard: int, tasks: List[Dict[str, Any]]
    ) -> None:
        """Execute stolen grants on this shard's fabric and relay the
        results home. The thief journals nothing — if it dies, the
        donor's steal lease expires and reclaims the subtasks with a
        fresh (fencing) attempt, so a resurrected thief's late result is
        deduped, never double-counted."""
        import queue as _q

        import requests

        url = self.peer_urls[donor_shard]
        wanted = {t["subtask_id"] for t in tasks if t.get("subtask_id")}
        sub = self.bus.subscribe(
            TOPIC_RESULTS, key_filter=lambda k: k in wanted
        )
        for t in tasks:
            counter_inc("tpuml_subtasks_stolen_total", direction="in")
            record_event(
                "steal.in", job_id=t.get("job_id"),
                subtask_id=t.get("subtask_id"),
                attempt=int(t.get("attempt") or 0),
                donor_shard=donor_shard,
            )
        logger.info(
            "Stole %d queued subtasks from shard %d", len(tasks), donor_shard
        )
        self.cluster.submit([dict(t) for t in tasks])

        def _pump():
            deadline = time.time() + 20.0 * self.config.service.client_timeout_s
            pending = set(wanted)
            try:
                while pending and time.time() < deadline:
                    try:
                        stid, result = sub.get(timeout=1.0)
                    except _q.Empty:
                        continue
                    if stid not in pending:
                        # echo of an already-relayed result (the donor
                        # forward-relays it back here if it migrated the
                        # job after granting the steal) — re-posting
                        # would ping-pong it between the shards
                        continue
                    try:
                        requests.post(
                            f"{url}/peer_result",
                            json=json_safe(result or {}),
                            timeout=10,
                        )
                        pending.discard(stid)
                    except requests.RequestException:
                        logger.warning(
                            "Relaying stolen result %s to shard %d failed",
                            stid, donor_shard,
                        )
            finally:
                sub.close()
                self.cluster.ledger.forget(wanted)

        threading.Thread(target=_pump, daemon=True).start()

    def _reclaim_stale_steals(self) -> None:
        """Donor lease sweep: a tombstone older than ``steal_lease_s``
        whose subtask is still open means the thief went dark — reclaim
        with a fresh attempt (fencing any resurrected thief) and
        re-dispatch locally; the job's still-running ingest loop picks
        the result up by subtask id."""
        svc = self.config.service
        now = time.time()
        for stid, t in list(self.store.steal_tombstones.items()):
            if now - float(t.get("ts") or 0) < svc.steal_lease_s:
                continue
            self.store.clear_steal(stid)
            info = self.store.lookup_specs([stid])
            if stid not in info:
                continue  # already terminal: nothing to reclaim
            rec = info[stid]
            task = dict(rec["spec"])
            self.cluster.ledger.seed(task)
            self.cluster.ledger.next_attempt(task, reason="steal_reclaim")
            task["metadata"] = rec["metadata"]
            counter_inc(
                "tpuml_subtasks_retried_total", reason="steal_reclaim"
            )
            record_event(
                "steal.reclaim", job_id=rec["job_id"], subtask_id=stid,
                attempt=int(task.get("attempt") or 0),
                thief_shard=t.get("thief"),
            )
            logger.warning(
                "Steal lease expired for %s (thief shard %s): reclaimed",
                stid, t.get("thief"),
            )
            self.cluster.submit([task])

    def ingest_peer_result(self, result: Dict[str, Any]) -> None:
        """``POST /peer_result``: a peer shard handing back a result —
        a thief returning a stolen grant, or a donor replay-forwarding a
        late result for a migrated job. Published onto the local result
        topic keyed by subtask id; the owning job loop applies the exact
        same first-wins / stale-attempt rules as any worker result."""
        result = dict(result or {})
        stid = result.get("subtask_id")
        if not stid:
            return
        counter_inc("tpuml_peer_results_ingested_total")
        self.bus.publish(TOPIC_RESULTS, result, key=stid)

    # ------------- trial telemetry plane (docs/OBSERVABILITY.md) -------------

    def ingest_curve(
        self, sid: str, job_id: str, subtask_id: str, curve: Dict[str, Any],
        *, rung: int = 0, attempt: int = 0,
    ) -> bool:
        """Ingest one trial's learning-curve record into the curve store
        and return the watchdog's divergence verdict. The store dedups on
        (subtask, rung, attempt) — the same curve arriving over both the
        metrics and the result transport counts, journals, and events
        exactly once. The divergence verdict is recomputed either way:
        the CALLER decides whether it terminates the trial (search loops
        do; plain jobs only mark the curve)."""
        if not isinstance(curve, dict) or not subtask_id:
            return False
        diverged = divergence(
            curve, self.config.service.curve_divergence_factor
        )
        added = self.curves.ingest(
            job_id, subtask_id, curve,
            rung=rung, attempt=attempt, diverged=diverged,
        )
        if added:
            counter_inc("tpuml_curve_points_total", float(added))
            record_event(
                "curve.ingest", job_id=job_id, subtask_id=subtask_id,
                rung=int(rung or 0), attempt=int(attempt or 0),
                n_points=added, diverged=diverged,
            )
            try:
                # journal so a restarted coordinator replays /curves and
                # the divergence history (torn tails are skipped by the
                # store's line-checksum replay)
                self.store.record_curve(
                    sid, job_id, subtask_id, curve,
                    rung=rung, attempt=attempt, diverged=diverged,
                )
            except KeyError:
                pass  # foreign/evicted job: serve from memory only
        return diverged

    def job_curves(self, job_id: str) -> Optional[Dict[str, Any]]:
        """All recorded learning curves for a job (``GET /curves/<jid>``),
        joined with the job's live status. None when the job id is
        unknown; a known job with no curves yet (CS230_CURVES=0, or no
        rung has reported) returns an empty ``curves`` list."""
        sid = next(
            (
                j["session_id"]
                for j in self.store.jobs_overview()
                if j["job_id"] == job_id
            ),
            None,
        )
        if sid is None:
            return None
        progress = self.store.job_progress(sid, job_id)
        out = self.curves.job(job_id) or {
            "job_id": job_id, "n_curves": 0, "curves": []
        }
        out["job_status"] = progress.get("job_status")
        out["tasks_diverged"] = progress.get("tasks_diverged", 0)
        return out

    def subtask_curves(self, job_id: str, subtask_id: str) -> Dict[str, Any]:
        """One trial's curve history across rungs/attempts
        (``GET /curves/<jid>/<stid>``). Raises KeyError when the pair
        never reported a curve — the route's 404."""
        out = self.curves.subtask(job_id, subtask_id)
        if out is None:
            raise KeyError(
                f"no curves recorded for subtask {subtask_id!r} of job "
                f"{job_id!r}"
            )
        return out

    # ------------- admission control (docs/ROBUSTNESS.md "Overload") -------------

    def admission_check(self, sid: Optional[str] = None) -> Optional[Dict[str, Any]]:
        """Admission decision for one would-be submit. None = admitted.
        Otherwise a rejection dict {reason, retry_after_s, status} the
        server maps to 429 (+ Retry-After) — or 503 while recovering.
        Caps (``service`` config): global / per-session in-flight job
        counts and the pending-subtask queue-depth watermark."""
        svc = self.config.service
        if not self.ready:
            return {
                "reason": "recovering",
                "retry_after_s": svc.admission_retry_after_s,
                "status": 503,
            }
        counts = self.store.unfinished_counts()
        reason = None
        if 0 < svc.max_inflight_jobs <= counts["jobs"]:
            reason = "global_inflight"
        elif (
            sid is not None
            and 0 < svc.max_inflight_jobs_per_session
            <= counts["per_session"].get(sid, 0)
        ):
            reason = "session_inflight"
        elif 0 < svc.admission_queue_watermark <= counts["pending_subtasks"]:
            reason = "queue_depth"
        if reason is None:
            return None
        counter_inc("tpuml_jobs_rejected_total", reason=reason)
        record_event(
            "admission.reject", reason=reason, session_id=sid,
            inflight_jobs=counts["jobs"],
            pending_subtasks=counts["pending_subtasks"],
        )
        logger.warning(
            "Rejecting submit for session %s: %s (%d jobs in flight, "
            "%d subtasks pending)", sid, reason, counts["jobs"],
            counts["pending_subtasks"],
        )
        return {
            "reason": reason,
            "retry_after_s": svc.admission_retry_after_s,
            "status": 429,
        }

    def overload_shedding(self) -> bool:
        """True while accepted load sits above ``shed_fraction`` of any
        enabled admission cap — the graceful-degradation band where the
        engine sheds OPTIONAL work (speculative duplicates, prewarm hints)
        before admission starts rejecting submits."""
        svc = self.config.service
        frac = svc.shed_fraction
        if frac <= 0:
            return False
        counts = self.store.unfinished_counts()
        if svc.max_inflight_jobs > 0 and (
            counts["jobs"] >= frac * svc.max_inflight_jobs
        ):
            return True
        return svc.admission_queue_watermark > 0 and (
            counts["pending_subtasks"]
            >= frac * svc.admission_queue_watermark
        )

    # ------------- session / data management (master.py:56-112 parity) -------------

    def create_session(
        self,
        session_id: Optional[str] = None,
        priority: int = 0,
    ) -> str:
        """``session_id`` lets a sharded front end mint the id (so
        ``shard_of(session_id)`` and the owning shard agree by
        construction); ``priority`` is the session's QoS lane — its jobs'
        subtasks dispatch ahead of lower lanes (docs/ARCHITECTURE.md
        "QoS priority lanes")."""
        if session_id is None and self.shard_id is not None:
            # a shard minting its own session id must mint one that
            # HASHES here — otherwise every front end would route the
            # session elsewhere and it would be unreachable through the
            # fleet. Rejection-sample (expected n_shards draws).
            from .sharding import shard_of

            while True:
                session_id = str(uuid.uuid4())
                if shard_of(session_id, self.n_shards) == self.shard_id:
                    break
        return self.store.create_session(session_id, priority=priority)

    def canonical_job_id(self, job_id: str) -> str:
        """The id a job is stored and routed under: on a shard, client-
        minted ids gain this shard's ``s<k>-`` stamp (deterministic, so
        idempotent-resubmit dedupe survives sharding); already-stamped
        and unsharded ids pass through."""
        if self.shard_id is None or not job_id:
            return job_id
        # a job adopted from a donor shard keeps the DONOR's stamp —
        # re-wrapping (stamp_job_id wraps foreign-looking stamps by
        # design) would mint an id this shard never stored and every
        # status poll on the migrated job would 404
        if self.store.is_adopted_job(job_id):
            return job_id
        from .sharding import stamp_job_id

        return stamp_job_id(self.shard_id, job_id)

    def check_session(self, sid: str) -> bool:
        return self.store.has_session(sid)

    def download_data(self, sid: str, dataset_url: str, dataset_name: str, dataset_type: str) -> Dict[str, Any]:
        self._require_session(sid)
        path = download_dataset(
            dataset_url, dataset_name, dataset_type, root=self.config.storage.datasets_dir
        )
        self.cache.invalidate(dataset_name)
        return {"status": "success", "dataset_path": path}

    def check_data(self, sid: str, dataset_name: str) -> Dict[str, Any]:
        self._require_session(sid)
        from ..data.datasets import find_csv

        path = find_csv(dataset_name, root=self.config.storage.datasets_dir)
        return {"exists": path is not None, "path": path}

    def preprocess(self, sid: str, dataset_id: str, config: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Run the YAML preprocessing pipeline on a staged dataset. Accepts
        an inline config dict or reads <configs_dir>/<dataset_id>/*.yaml like
        the reference (master.py:352-379)."""
        self._require_session(sid)
        import glob
        import os

        import pandas as pd

        from ..data.datasets import dataset_dir, find_csv

        csv = find_csv(dataset_id, root=self.config.storage.datasets_dir)
        if csv is None:
            raise FileNotFoundError(f"Dataset {dataset_id!r} not staged")
        if config is None:
            import yaml

            hits = sorted(
                glob.glob(os.path.join(self.config.storage.configs_dir, dataset_id, "*.yaml"))
            )
            if not hits:
                raise FileNotFoundError(f"No preprocess config for {dataset_id!r}")
            config = yaml.safe_load(open(hits[0]).read())
        df = preprocess_dataframe(pd.read_csv(csv), config)
        out_dir = os.path.join(dataset_dir(dataset_id, self.config.storage.datasets_dir), "preprocessed")
        os.makedirs(out_dir, exist_ok=True)
        out_path = os.path.join(out_dir, f"{dataset_id}_preprocessed.csv")
        df.to_csv(out_path, index=False)
        self.cache.invalidate(dataset_id)
        return {"status": "success", "preprocessed_path": out_path, "n_rows": len(df)}

    # ------------- training (master.py:170-268 parity) -------------

    def submit_train(self, sid: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Expand a train job into subtasks, persist, and dispatch async.
        Payload schema matches the reference client (core.py:152-174):
        {job_id?, dataset_id, model_details, train_params}."""
        self._require_session(sid)
        job_id = self.canonical_job_id(
            payload.get("job_id") or str(uuid.uuid4())
        )
        if payload.get("job_id"):
            # idempotent resubmit: the client minted this job_id and is
            # retrying a submit whose response it never saw (coordinator
            # restart, dropped SSE stream, 429 backoff loop). Re-expanding
            # would duplicate every subtask — return the original
            # acceptance instead (docs/ROBUSTNESS.md "Reconnecting edges").
            # The check and the in-progress claim happen under one lock:
            # a duplicate arriving DURING the first copy's expansion (the
            # store doesn't know the job yet) must dedupe too, not race
            # has_job-then-create.
            with self._submit_lock:
                known = self.store.has_job(sid, job_id)
                if known or job_id in self._submitting:
                    logger.info("Duplicate submit of job %s deduped", job_id)
                    return {
                        "status": "submitted",
                        "job_id": job_id,
                        # unknown while the first copy is still expanding
                        "total_subtasks": (
                            self.store.job_progress(sid, job_id)[
                                "total_subtasks"
                            ] if known else None
                        ),
                        "duplicate": True,
                    }
                self._submitting.add(job_id)
            try:
                return self._submit_train_locked(sid, job_id, payload)
            finally:
                with self._submit_lock:
                    self._submitting.discard(job_id)
        return self._submit_train_locked(sid, job_id, payload)

    def _submit_train_locked(
        self, sid: str, job_id: str, payload: Dict[str, Any]
    ) -> Dict[str, Any]:
        """Expansion + persistence + dispatch for an admitted, deduped
        submit (``_submitting`` guard held by the caller for client-minted
        job ids)."""
        dataset_id = payload["dataset_id"]
        model_details = payload["model_details"]
        train_params = dict(payload.get("train_params") or {})
        cv_params = model_details.get("cv_params") or {}
        if "cv" in cv_params and "cv" not in train_params:
            train_params["cv"] = cv_params["cv"]
        scoring = train_params.get("scoring", cv_params.get("scoring"))
        if (
            callable(scoring) and not isinstance(scoring, str)
            and self.cluster is not None
        ):
            # a cluster's remote agents pull tasks over REST, where
            # json_safe would stringify the function into a confusing
            # "unsupported scoring '<function ...>'" server error per
            # trial; fail the submission with the real reason instead
            # (the default in-process executor honors callables)
            raise ValueError(
                "callable scoring is not supported on a clustered "
                "coordinator (tasks are serialized to worker agents); "
                "use a scorer name, or a coordinator without a cluster"
            )

        # one trace id per job, minted here unless the client already sent
        # one (X-Trace-Id via the REST server, or an activate() in local
        # mode); stamped into every subtask spec so it rides the task bus /
        # /next_tasks long-poll to remote agents (docs/OBSERVABILITY.md)
        trace_id = current_trace_id() or new_trace_id()
        TRACER.bind_job(job_id, trace_id)
        with span("job.submit", trace_id=trace_id, job_id=job_id,
                  dataset_id=dataset_id,
                  model_type=model_details.get("model_type")) as sub_sp:
            with span("job.expand", job_id=job_id):
                subtasks = create_subtasks(
                    job_id, sid, dataset_id, model_details, train_params
                )
            # QoS lane: the payload may override, else the session's
            # priority rides every subtask spec — the dispatch queues
            # (task ingress + per-worker train queues) order on it, and
            # retries/requeues/speculation copy the spec so the lane
            # survives the whole fault-tolerance machinery
            priority = payload.get("priority")
            if priority is None:
                priority = self.store.session_priority(sid)
            for st in subtasks:
                st["trace_id"] = trace_id
                st["priority"] = int(priority or 0)
            sub_sp.attrs["total_subtasks"] = len(subtasks)
            try:
                metadata = self.cache.metadata(dataset_id)
            except FileNotFoundError:
                metadata = {}
            self.store.create_job(sid, job_id, payload, subtasks, metadata)
        counter_inc("tpuml_jobs_submitted_total")

        t = threading.Thread(
            target=self._run_job, args=(sid, job_id, subtasks), daemon=True
        )
        self._job_threads[job_id] = t
        t.start()
        return {
            "status": "submitted",
            "job_id": job_id,
            "total_subtasks": len(subtasks),
        }

    def _run_job(
        self,
        sid: str,
        job_id: str,
        subtasks: List[Dict[str, Any]],
        existing: Optional[Dict[str, Dict[str, Any]]] = None,
    ) -> None:
        """Execute a job's subtasks and aggregate. ``existing`` (resume path)
        maps already-finished subtask ids to their journaled results; only
        the remainder is dispatched."""

        def on_result(subtask_id: str, status: str, result: Optional[Dict[str, Any]]):
            self.store.update_subtask(sid, job_id, subtask_id, status, result)
            r = result or {}
            if isinstance(r.get("curve"), dict):
                # terminal curve ingest (deduped against the metrics-path
                # delivery): verdict only — termination decisions belong
                # to the search loops, and this result is terminal anyway
                self.ingest_curve(
                    sid, job_id, subtask_id, r["curve"],
                    rung=int((r.get("asha") or {}).get("rung") or 0),
                    attempt=int(r.get("attempt") or 0),
                )
            record_event(
                "result", job_id=job_id, subtask_id=subtask_id,
                worker_id=r.get("worker_id"),
                attempt=int(r.get("attempt") or 0), status=status,
                mean_cv_score=r.get("mean_cv_score"),
                error=r.get("error"),
            )
            self.bus.publish(TOPIC_RESULTS, result, key=subtask_id)

        def on_metrics(msg: Dict[str, Any]):
            if isinstance(msg.get("curve"), dict):
                # live curve ingest: the trace reaches /curves and the SSE
                # stream at the batch boundary, before the result settles
                self.ingest_curve(
                    sid, job_id, msg.get("subtask_id"), msg["curve"],
                    rung=int(msg.get("rung") or 0),
                    attempt=int(msg.get("attempt") or 0),
                )
            self.bus.publish(TOPIC_METRICS, msg, key=msg.get("subtask_id"))

        def on_intermediate(subtask_id: str, result: Optional[Dict[str, Any]]):
            # non-terminal rung boundary (promoted/paused): journal the
            # report + record the event, but do NOT publish to the result
            # topic — in cluster mode that topic is this coordinator's own
            # ingest channel, and republishing would echo the report back
            # into the rung loop forever
            self.store.update_subtask(
                sid, job_id, subtask_id, "promoted", result
            )
            r = result or {}
            if isinstance(r.get("curve"), dict):
                # rung-boundary curve of a promoted trial — journaled here
                # so a replayed coordinator has each rung's trace
                self.ingest_curve(
                    sid, job_id, subtask_id, r["curve"],
                    rung=int((r.get("asha") or {}).get("rung") or 0),
                    attempt=int(r.get("attempt") or 0),
                )
            record_event(
                "result", job_id=job_id, subtask_id=subtask_id,
                worker_id=r.get("worker_id"),
                attempt=int(r.get("attempt") or 0), status="promoted",
                mean_cv_score=r.get("mean_cv_score"),
                rung=(r.get("asha") or {}).get("rung"),
            )

        existing = existing or {}
        remaining = [st for st in subtasks if st["subtask_id"] not in existing]
        # adaptive-search job (docs/SEARCH.md): specs carry an ``asha``
        # rung block — route through the rung controller instead of the
        # run-everything-to-completion paths below
        driver: Optional[SearchJobDriver] = None
        if any(st.get("asha") for st in subtasks):
            driver = SearchJobDriver(subtasks)
            # rebuild rung state from the journaled rung history — always,
            # not just when a terminal result exists: a coordinator killed
            # after rung-0 reports but before the first prune/complete has
            # promotions to re-derive too (a fresh job's empty history is
            # a no-op). Determinism means nothing is promoted twice.
            driver.resume(self.store.get_job(sid, job_id))
        # job threads start with an empty contextvar context: re-activate the
        # trace the subtask specs carry (journaled specs keep it across a
        # coordinator restart, so resumed jobs stitch into the same trace)
        trace_id = next(
            (st.get("trace_id") for st in subtasks if st.get("trace_id")), None
        ) or TRACER.trace_for_job(job_id) or new_trace_id()
        TRACER.bind_job(job_id, trace_id)
        try:
            with activate(trace_id):
                with span("job.execute", trace_id=trace_id, job_id=job_id,
                          n_subtasks=len(remaining),
                          n_resumed=len(existing),
                          search="asha" if driver is not None else None,
                          mode="scheduled" if self.cluster is not None
                          else "direct"):
                    if driver is not None:
                        by_id = dict(existing)
                        if self.cluster is not None:
                            by_id.update(self._run_job_search_scheduled(
                                sid, job_id, driver, on_result,
                                on_intermediate,
                            ))
                        else:
                            by_id.update(self._run_job_search_direct(
                                sid, job_id, driver, on_result,
                                on_intermediate, on_metrics,
                            ))
                        new_results = []
                    elif not remaining:
                        new_results = []
                    elif self.cluster is not None:
                        new_results = self._run_job_scheduled(
                            sid, job_id, remaining, on_result
                        )
                    else:
                        new_results = self.executor.run_subtasks(
                            remaining, on_result=on_result, on_metrics=on_metrics
                        )
                if driver is None:
                    by_id = dict(existing)
                    for st, r in zip(remaining, new_results):
                        by_id[st["subtask_id"]] = r
                results = [by_id.get(st["subtask_id"]) for st in subtasks]
                with span("job.aggregate", trace_id=trace_id, job_id=job_id):
                    self._aggregate(
                        sid, job_id, subtasks, results,
                        search_summary=(
                            driver.summary() if driver is not None else None
                        ),
                    )
            counter_inc("tpuml_jobs_completed_total")
        except JobMigratedError:
            # not a failure: the job left this shard mid-flight. The
            # migration driver (migrate_job) owns the rest of the
            # handoff; finalization happens on the destination shard.
            logger.info("Job %s quiesced for migration", job_id)
        except Exception as e:  # noqa: BLE001
            logger.exception("Job %s failed", job_id)
            counter_inc("tpuml_jobs_failed_total")
            self.store.finalize_job(
                sid, job_id, {"status": "failed", "error": str(e)}
            )

    def _run_job_scheduled(self, sid, job_id, subtasks, on_result) -> List[Dict[str, Any]]:
        """Dispatch through the placement engine and collect results from
        the bus — the reference's consume_results thread
        (task_handler.py:18-68) — upgraded with the fault-tolerance layer
        (docs/ROBUSTNESS.md):

        - **at-least-once + dedup by attempt id**: the first terminal
          COMPLETED result for a subtask wins; later duplicates (requeue
          races, speculative losers) are dropped. A FAILED result only
          counts against the retry budget when it belongs to the CURRENT
          attempt — failures of superseded attempts are stale.
        - **bounded retries with backoff**: a failed attempt is re-
          dispatched up to ``retry_max_attempts`` total executions, with
          exponential per-attempt backoff and the failing worker excluded.
        - **poison quarantine**: a subtask that exhausts its budget — or
          killed ``poison_kill_threshold`` worker backends — is accepted
          as a quarantined failure; the job completes with partial results
          instead of stalling.
        """
        import queue as _q

        cfg = self.config.scheduler
        ledger = self.cluster.ledger
        wanted = {st["subtask_id"]: i for i, st in enumerate(subtasks)}
        spec_by_id = {st["subtask_id"]: st for st in subtasks}
        results: List[Optional[Dict[str, Any]]] = [None] * len(subtasks)
        #: failure retries awaiting their backoff: (due_ts, stamped task)
        retry_due: List[tuple] = []
        sub = self.bus.subscribe("result", key_filter=lambda k: k in wanted)
        try:
            job = self.store.get_job(sid, job_id)
            metadata = job.get("metadata") or None
            for st in subtasks:
                ledger.seed(st)
            self.cluster.submit(subtasks, metadata=metadata)
            pending = set(wanted)
            # Progress-aware liveness, not a wall-clock deadline: a long job
            # whose executors are still productively computing must not be
            # failed server-side. The job times out only when BOTH hold for
            # client_timeout_s: no result arrived, AND no live worker owns
            # any of its pending tasks (a placed task stays in its worker's
            # queue until the metrics feedback clears it).
            stall_grace = self.config.service.client_timeout_s
            # ownership proves placement, not computation: a wedged worker
            # whose heartbeat thread survives would hold its queue entry
            # forever. The lease layer normally reclaims those; a generous
            # hard bound restores eventual liveness even with leases off.
            hard_deadline = time.time() + 20.0 * stall_grace
            last_progress = time.time()
            while pending:
                # quiesce gate: the rebalancer marked this job for
                # migration — unwind without finalizing; the migration
                # driver fences the remaining attempts and the
                # destination shard finishes the job
                if self._migrating.get((sid, job_id)) is not None:
                    raise JobMigratedError(job_id)
                now = time.time()
                if now > hard_deadline:
                    raise TimeoutError(
                        f"{len(pending)} subtasks unfinished at the hard "
                        f"deadline ({20.0 * stall_grace:.0f}s)"
                    )
                if retry_due:
                    due = [t for ts, t in retry_due if ts <= now]
                    if due:
                        retry_due = [
                            (ts, t) for ts, t in retry_due if ts > now
                        ]
                        self.cluster.submit(due, metadata=metadata)
                try:
                    stid, result = sub.get(timeout=0.5)
                except _q.Empty:
                    if time.time() - last_progress > stall_grace:
                        owned: set = {
                            t["subtask_id"] for _, t in retry_due
                        }  # backoff-parked retries count as owned
                        for q in self.cluster.engine.queue_snapshot().values():
                            owned.update(q)
                        # subtasks granted to a thief shard are owned
                        # remotely: the steal lease (not this stall
                        # check) reclaims them if the thief goes dark
                        owned.update(self.store.steal_tombstones)
                        if not (pending & owned):
                            raise TimeoutError(
                                f"{len(pending)} subtasks stalled with no live "
                                f"owner for {stall_grace:.0f}s "
                                f"(e.g. {sorted(pending)[:3]})"
                            )
                        last_progress = time.time()  # workers still own tasks
                    continue
                result = result or {}
                # any result settles an outstanding steal grant for this
                # subtask (terminal → done; failed → back in the local
                # retry path below)
                self.store.clear_steal(stid)
                if stid not in pending:
                    # duplicate delivery: a requeue race, the losing copy
                    # of a speculative pair, or a zombie attempt from
                    # before a coordinator restart — dropped here, which
                    # IS the cancellation ("first terminal result wins")
                    counter_inc("tpuml_results_duplicate_dropped_total")
                    record_event(
                        "result.duplicate", job_id=job_id, subtask_id=stid,
                        worker_id=result.get("worker_id"),
                        attempt=int(result.get("attempt") or 0),
                    )
                    if ledger.was_speculated(stid):
                        counter_inc("tpuml_speculative_wasted_total")
                        record_event(
                            "speculate.loss", job_id=job_id,
                            subtask_id=stid,
                            worker_id=result.get("worker_id"),
                            attempt=int(result.get("attempt") or 0),
                        )
                    continue
                if result.get("status", "completed") != "failed":
                    pending.discard(stid)
                    ledger.mark_done(stid)
                    results[wanted[stid]] = result
                    if result.get("speculative"):
                        counter_inc("tpuml_speculative_won_total")
                        record_event(
                            "speculate.win", job_id=job_id, subtask_id=stid,
                            worker_id=result.get("worker_id"),
                            attempt=int(result.get("attempt") or 0),
                        )
                    on_result(stid, "completed", result)
                    last_progress = time.time()
                    continue
                # ---- failed result: retry budget / quarantine ----
                attempt = int(result.get("attempt") or 0)
                if ledger.is_stale(stid, attempt):
                    # a newer attempt (lease reclaim / speculation) owns
                    # this subtask now; the old attempt's failure must not
                    # consume budget
                    record_event(
                        "result.stale", job_id=job_id, subtask_id=stid,
                        worker_id=result.get("worker_id"), attempt=attempt,
                        error=result.get("error"),
                    )
                    continue
                wid = result.get("worker_id")
                entry = ledger.record_failure(stid, wid)
                poisoned = entry.device_losses >= cfg.poison_kill_threshold
                if poisoned or entry.failures >= cfg.retry_max_attempts:
                    quarantined = {
                        **result,
                        "quarantined": True,
                        "attempts": entry.failures,
                        "quarantine_reason": (
                            "poisoned" if poisoned else "retries_exhausted"
                        ),
                    }
                    counter_inc("tpuml_subtasks_quarantined_total")
                    logger.error(
                        "Quarantining %s after %d failed attempts (%s): %s",
                        stid, entry.failures,
                        quarantined["quarantine_reason"],
                        result.get("error"),
                    )
                    with span("job.quarantine", job_id=job_id,
                              subtask_id=stid, attempts=entry.failures,
                              reason=quarantined["quarantine_reason"]):
                        pass
                    record_event(
                        "quarantine", job_id=job_id, subtask_id=stid,
                        worker_id=wid, attempt=attempt,
                        reason=quarantined["quarantine_reason"],
                        attempts=entry.failures,
                        device_losses=entry.device_losses,
                        error=result.get("error"),
                    )
                    pending.discard(stid)
                    ledger.mark_done(stid)
                    results[wanted[stid]] = quarantined
                    on_result(stid, "failed", quarantined)
                else:
                    task = dict(spec_by_id[stid])
                    task.pop("speculative", None)
                    ledger.next_attempt(
                        task, exclude_worker=wid, reason="failure"
                    )
                    backoff = min(
                        cfg.retry_backoff_s * 2 ** max(entry.failures - 1, 0),
                        cfg.retry_backoff_max_s,
                    )
                    counter_inc(
                        "tpuml_subtasks_retried_total", reason="failure"
                    )
                    logger.warning(
                        "Retrying %s (attempt %d/%d) in %.2fs, excluding "
                        "worker %s",
                        stid, task["attempt"], cfg.retry_max_attempts,
                        backoff, wid,
                    )
                    with span("job.retry", job_id=job_id, subtask_id=stid,
                              attempt=task["attempt"], backoff_s=backoff,
                              excluded_worker=wid):
                        pass
                    record_event(
                        "retry", job_id=job_id, subtask_id=stid,
                        worker_id=wid, attempt=task["attempt"],
                        reason="failure", backoff_s=backoff,
                        failures=entry.failures,
                        max_attempts=cfg.retry_max_attempts,
                        error=result.get("error"),
                    )
                    retry_due.append((time.time() + backoff, task))
                last_progress = time.time()
            return results  # type: ignore[return-value]
        finally:
            sub.close()
            self.cluster.ledger.forget(wanted)

    # ------------- adaptive search (docs/SEARCH.md) -------------

    def _apply_search_step(
        self, step: Step, sid, job_id, pending, results_by_id, on_result,
        on_intermediate, metadata,
    ) -> None:
        """Apply one rung-controller step to the scheduled job loop:
        journal intermediate (promoted) results FIRST, then issue cancels,
        finalize terminals, and submit the fresh rung dispatches LAST — so
        a crash between any two phases replays into a state the resume
        path handles (an unjournaled dispatch is re-issued; a journaled
        report re-derives its promotion)."""
        ledger = self.cluster.ledger
        for tid, res in step.promoted:
            if res is not None:
                on_intermediate(tid, res)
        new_tasks = []
        for task in step.new_tasks:
            task.pop("speculative", None)
            ledger.next_attempt(task, reason="promotion")
            new_tasks.append(task)
        for c in step.cancels:
            self.cluster.cancel_subtask(
                c["subtask_id"], c.get("attempt", 0), job_id=job_id
            )
        for tid, status, res in step.finished:
            pending.discard(tid)
            ledger.mark_done(tid)
            results_by_id[tid] = res
            on_result(tid, status, res)
            # deliberately NOT clearing the cancel registry here: a prune's
            # synthesized terminal lands in the SAME step as its cancel, and
            # clearing now would empty the registry before any remote
            # agent's next poll ever saw the entry. The registry clears when
            # the WORKER's own terminal result arrives (push_result) or at
            # job end (the loop's finally).
        if new_tasks:
            self.cluster.submit(new_tasks, metadata=metadata)

    def _run_job_search_scheduled(
        self, sid, job_id, driver: SearchJobDriver, on_result,
        on_intermediate,
    ) -> Dict[str, Dict[str, Any]]:
        """Scheduled-mode rung loop: like ``_run_job_scheduled`` (same
        at-least-once ingest, attempt dedup, bounded retries, poison
        quarantine) but result ingest feeds the rung controller — a
        completed rung dispatch may promote its trial (fresh attempt at
        the eta-times budget), pause it, or prune peers; quarantined
        trials leave the ladder so their rungs close for the survivors."""
        import queue as _q

        cfg = self.config.scheduler
        ledger = self.cluster.ledger
        all_ids = set(driver.specs)
        results_by_id: Dict[str, Dict[str, Any]] = {}
        pending = {tid for tid in all_ids if tid not in driver._finalized}
        retry_due: List[tuple] = []
        sub = self.bus.subscribe("result", key_filter=lambda k: k in all_ids)
        try:
            job = self.store.get_job(sid, job_id)
            metadata = job.get("metadata") or None
            # resume: terminal states the replayed controller derived
            # whose store writes the crash swallowed
            self._apply_search_step(
                driver.resume_step(), sid, job_id, pending, results_by_id,
                on_result, on_intermediate, metadata,
            )
            tasks = driver.pending_tasks()
            for st in tasks:
                ledger.seed(st)
            if tasks:
                self.cluster.submit(tasks, metadata=metadata)
            self.store.set_search_state(sid, job_id, driver.summary())
            stall_grace = self.config.service.client_timeout_s
            hard_deadline = time.time() + 20.0 * stall_grace
            last_progress = time.time()
            while pending:
                now = time.time()
                if now > hard_deadline:
                    raise TimeoutError(
                        f"{len(pending)} trials unfinished at the hard "
                        f"deadline ({20.0 * stall_grace:.0f}s)"
                    )
                if retry_due:
                    due = [t for ts, t in retry_due if ts <= now]
                    if due:
                        retry_due = [
                            (ts, t) for ts, t in retry_due if ts > now
                        ]
                        self.cluster.submit(due, metadata=metadata)
                try:
                    stid, result = sub.get(timeout=0.5)
                except _q.Empty:
                    if time.time() - last_progress > stall_grace:
                        owned: set = {
                            t["subtask_id"] for _, t in retry_due
                        }
                        for q in self.cluster.engine.queue_snapshot().values():
                            owned.update(q)
                        if not (pending & owned):
                            raise TimeoutError(
                                f"{len(pending)} trials stalled with no "
                                f"live owner for {stall_grace:.0f}s "
                                f"(e.g. {sorted(pending)[:3]})"
                            )
                        last_progress = time.time()
                    continue
                result = result or {}
                if stid not in pending:
                    counter_inc("tpuml_results_duplicate_dropped_total")
                    record_event(
                        "result.duplicate", job_id=job_id, subtask_id=stid,
                        worker_id=result.get("worker_id"),
                        attempt=int(result.get("attempt") or 0),
                    )
                    continue
                status = result.get("status", "completed")
                if status != "failed":
                    # a rung report (completed) or a cooperative-cancel
                    # terminal (pruned) — both feed the controller; the
                    # driver dedups duplicate/stale deliveries itself
                    curve = result.get("curve")
                    if status == "pruned":
                        step = driver.handle_pruned_result(stid, result)
                    elif isinstance(curve, dict) and self.ingest_curve(
                        sid, job_id, stid, curve,
                        rung=int((result.get("asha") or {}).get("rung") or 0),
                        attempt=int(result.get("attempt") or 0),
                    ):
                        # numerical-health watchdog: the rung's trace is
                        # non-finite or blowing up — terminate the trial
                        # as ``diverged`` (never a failure: no retry
                        # budget burns, no quarantine) instead of letting
                        # the ladder promote it
                        step = driver.handle_diverged(
                            stid, curve, result=result
                        )
                    else:
                        step = driver.handle_result(stid, result)
                    self._apply_search_step(
                        step, sid, job_id, pending, results_by_id,
                        on_result, on_intermediate, metadata,
                    )
                    self.store.set_search_state(
                        sid, job_id, driver.summary()
                    )
                    last_progress = time.time()
                    continue
                # ---- failed rung execution: retry budget / quarantine ----
                attempt = int(result.get("attempt") or 0)
                if ledger.is_stale(stid, attempt):
                    record_event(
                        "result.stale", job_id=job_id, subtask_id=stid,
                        worker_id=result.get("worker_id"), attempt=attempt,
                        error=result.get("error"),
                    )
                    continue
                wid = result.get("worker_id")
                entry = ledger.record_failure(stid, wid)
                poisoned = entry.device_losses >= cfg.poison_kill_threshold
                if poisoned or entry.failures >= cfg.retry_max_attempts:
                    quarantined = {
                        **result,
                        "quarantined": True,
                        "attempts": entry.failures,
                        "quarantine_reason": (
                            "poisoned" if poisoned else "retries_exhausted"
                        ),
                    }
                    counter_inc("tpuml_subtasks_quarantined_total")
                    logger.error(
                        "Quarantining trial %s after %d failed attempts "
                        "(%s): %s", stid, entry.failures,
                        quarantined["quarantine_reason"],
                        result.get("error"),
                    )
                    record_event(
                        "quarantine", job_id=job_id, subtask_id=stid,
                        worker_id=wid, attempt=attempt,
                        reason=quarantined["quarantine_reason"],
                        attempts=entry.failures,
                        device_losses=entry.device_losses,
                        error=result.get("error"),
                    )
                    step = driver.handle_quarantine(stid, quarantined)
                    self._apply_search_step(
                        step, sid, job_id, pending, results_by_id,
                        on_result, on_intermediate, metadata,
                    )
                    self.store.set_search_state(
                        sid, job_id, driver.summary()
                    )
                else:
                    task = dict(driver.specs[stid])
                    task.pop("speculative", None)
                    ledger.next_attempt(
                        task, exclude_worker=wid, reason="failure"
                    )
                    # keep the driver's spec in sync with the live attempt
                    # (the promotion path already does — _stamp stores the
                    # dict next_attempt mutates): a later prune's
                    # cooperative cancel must carry THIS attempt, or the
                    # executor's attempt guard lets the retry run its
                    # full doomed budget
                    driver.specs[stid] = task
                    backoff = min(
                        cfg.retry_backoff_s * 2 ** max(entry.failures - 1, 0),
                        cfg.retry_backoff_max_s,
                    )
                    counter_inc(
                        "tpuml_subtasks_retried_total", reason="failure"
                    )
                    logger.warning(
                        "Retrying rung dispatch %s (attempt %d/%d) in "
                        "%.2fs, excluding worker %s",
                        stid, task["attempt"], cfg.retry_max_attempts,
                        backoff, wid,
                    )
                    record_event(
                        "retry", job_id=job_id, subtask_id=stid,
                        worker_id=wid, attempt=task["attempt"],
                        reason="failure", backoff_s=backoff,
                        failures=entry.failures,
                        max_attempts=cfg.retry_max_attempts,
                        error=result.get("error"),
                    )
                    retry_due.append((time.time() + backoff, task))
                last_progress = time.time()
            return results_by_id
        finally:
            sub.close()
            self.cluster.ledger.forget(all_ids)
            self.cluster.clear_cancels(all_ids)

    def _apply_search_step_direct(
        self, step: Step, results_by_id, on_result, on_intermediate, job_id
    ) -> List[Dict[str, Any]]:
        """Direct-mode step application; returns the fresh rung dispatches
        for the next wave."""
        for tid, res in step.promoted:
            if res is not None:
                on_intermediate(tid, res)
        new_tasks = []
        for task in step.new_tasks:
            # no ledger in direct mode: bump the attempt stamp in place so
            # rung dispatches stay distinguishable in results/journals
            task["attempt"] = int(task.get("attempt") or 0) + 1
            task.pop("speculative", None)
            new_tasks.append(task)
        if step.cancels:
            self.executor.cancel(step.cancels)
        for tid, status, res in step.finished:
            results_by_id[tid] = res
            on_result(tid, status, res)
        return new_tasks

    def _run_job_search_direct(
        self, sid, job_id, driver: SearchJobDriver, on_result,
        on_intermediate, on_metrics,
    ) -> Dict[str, Dict[str, Any]]:
        """Direct-mode rung loop: synchronous waves on the in-process
        executor. The executor's per-batch metrics messages carry the
        rung-boundary score; ``on_metrics`` feeds the controller DURING
        the wave (the stop_score fast path), so cancels reach the
        executor before its next batch boundary. Failures keep the legacy
        direct-mode semantics (terminal, no retries) and simply drop the
        trial off its ladder."""
        results_by_id: Dict[str, Dict[str, Any]] = {}
        # resume synthesis first (a resume_step never carries new tasks —
        # dispatches come from pending_tasks below)
        self._apply_search_step_direct(
            driver.resume_step(), results_by_id, on_result, on_intermediate,
            job_id,
        )
        tasks = driver.pending_tasks()
        while tasks:
            steps: List[Step] = []

            def _metrics(msg):
                curve = msg.get("curve")
                stid_m = msg.get("subtask_id")
                if isinstance(curve, dict) and stid_m:
                    # numerical-health watchdog, metrics path: the trace
                    # arrives at the batch boundary while sibling groups
                    # of the wave may still be running — a diverged trial
                    # is terminated NOW (cooperative cancel reaches the
                    # executor before its next batch boundary) instead of
                    # burning the rest of its rung budget
                    if self.ingest_curve(
                        sid, job_id, stid_m, curve,
                        rung=int(msg.get("rung") or 0),
                        attempt=int(msg.get("attempt") or 0),
                    ):
                        dstep = driver.handle_diverged(
                            stid_m, curve, result=None
                        )
                        if dstep.cancels:
                            self.executor.cancel(dstep.cancels)
                        if dstep.finished or dstep.new_tasks or dstep.promoted:
                            steps.append(dstep)
                step = driver.handle_metrics(msg)
                if step.cancels:
                    # reach the executor before its next batch boundary
                    self.executor.cancel(step.cancels)
                if step.finished or step.new_tasks or step.promoted:
                    steps.append(step)
                on_metrics(msg)

            wave = self.executor.run_subtasks(tasks, on_metrics=_metrics)
            for st, r in zip(tasks, wave):
                stid = st["subtask_id"]
                r = r or {}
                status = r.get("status", "completed")
                if status == "failed":
                    steps.append(driver.handle_quarantine(stid, r))
                elif status == "pruned":
                    steps.append(driver.handle_pruned_result(stid, r))
                else:
                    steps.append(driver.handle_result(stid, r))
            tasks = []
            for step in steps:
                tasks.extend(
                    self._apply_search_step_direct(
                        step, results_by_id, on_result, on_intermediate,
                        job_id,
                    )
                )
            self.store.set_search_state(sid, job_id, driver.summary())
        if not driver.done():
            logger.warning(
                "Search job %s: wave loop drained with %d trials "
                "undecided", job_id,
                sum(1 for t in driver.specs
                    if t not in driver.controller.decided),
            )
        return results_by_id

    def _aggregate(self, sid, job_id, subtasks, results,
                   search_summary: Optional[Dict[str, Any]] = None) -> None:
        """Sort completed trials by mean_cv_score desc; best_result first
        (task_handler.py:254-263). The winner is refit once and stored as a
        downloadable artifact."""
        completed = [r for r in results if r and r.get("status") == "completed"]
        failed = [r for r in results if r and r.get("status") == "failed"]
        pruned = [r for r in results if r and r.get("status") == "pruned"]
        diverged = [r for r in results if r and r.get("status") == "diverged"]

        def score_key(r):
            # None survives JSON round-trips from remote agents (inf/NaN are
            # nulled by json_safe); rank those trials last
            v = r.get("mean_cv_score")
            return v if isinstance(v, (int, float)) else float("-inf")

        ranked = sorted(completed, key=score_key, reverse=True)
        best = dict(ranked[0]) if ranked else None
        # Winner selection by the ON-DEVICE collective argmax: on a
        # multi-device mesh the trial engine reduces each sharded score
        # chunk over ICI (trial_map._chunk_best) and marks the per-group
        # winner (device_argmax). The host only max-combines those few
        # marked results. On a single chip the scores are host scalars
        # already and the host sort IS the production path (a device round
        # trip to reduce a handful of floats buys nothing).
        marked = [r for r in completed if r.get("device_argmax")]
        if best is not None and marked:
            # max() keeps the first of equals and `completed` is in
            # submission order, so ties resolve like sklearn's first-max
            dev_best = max(marked, key=score_key)
            if dev_best["subtask_id"] == best["subtask_id"]:
                best["winner_via"] = "ici_argmax"
            else:  # near-tie under f32-vs-f64 rounding, or the true winner
                # ran in an unsharded group: keep the host-ranked winner
                logger.info(
                    "device argmax winner %s (%.6f) differs from host-ranked "
                    "%s (%.6f); keeping host winner",
                    dev_best["subtask_id"], score_key(dev_best),
                    best["subtask_id"], score_key(best),
                )
        if best is not None:
            # artifact refit is lazy: materialized on the first
            # download_best_model call (the reference eagerly pickled every
            # trial's model, worker.py:352-356 — pure overhead for searches)
            st = next(s for s in subtasks if s["subtask_id"] == best["subtask_id"])
            if best.get("asha") and best.get("parameters"):
                # adaptive search: refit at the winner's FINAL rung budget
                # (the subtask list still holds the rung-0 spec)
                st = {**st, "parameters": best["parameters"]}
            with self._artifact_lock:
                self._artifact_specs[(sid, job_id)] = st
        final = {
            "results": ranked,
            "failed": failed,
            "best_result": best,
            "completion_time": time.time(),
        }
        if pruned or search_summary is not None:
            # adaptive search (docs/SEARCH.md): early-stopped trials are a
            # separate, NON-failure report — ranked by their last rung
            # score — plus the final rung-state summary
            final["pruned_results"] = sorted(
                pruned, key=score_key, reverse=True
            )
            final["n_pruned"] = len(pruned)
            if search_summary is not None:
                final["search"] = search_summary
        if diverged:
            # watchdog terminations (docs/OBSERVABILITY.md "Trial
            # telemetry plane"): numerically-unhealthy trials are their
            # own NON-failure report — like pruned, they never count
            # against retry budgets or quarantine
            final["diverged_results"] = diverged
            final["n_diverged"] = len(diverged)
        # quarantine contract (docs/ROBUSTNESS.md): subtasks the retry
        # layer gave up on surface as a structured report, and the job
        # finalizes as ``completed_with_failures`` (partial results)
        # instead of plain ``completed``. Direct-mode failures (no retry
        # machinery ran, no ``quarantined`` stamp) keep the legacy
        # ``completed`` + failed-list semantics.
        quarantined = [r for r in failed if r.get("quarantined")]
        if quarantined:
            final["failed_subtasks"] = [
                {
                    "subtask_id": r.get("subtask_id"),
                    "attempts": r.get("attempts"),
                    "reason": r.get("quarantine_reason"),
                    "error": r.get("error"),
                }
                for r in quarantined
            ]
            logger.warning(
                "Job %s completed with %d quarantined subtasks "
                "(partial results)", job_id, len(quarantined),
            )
        self.store.finalize_job(sid, job_id, json_safe(final))

    # ------------- status / metrics / model (master.py:115-340 parity) -------------

    def check_status(self, sid: str, job_id: str) -> Dict[str, Any]:
        self._require_session(sid)
        progress = self.store.job_progress(sid, job_id)
        status = progress["job_status"]
        if (
            status in ("completed", "completed_with_failures")
            and progress["job_result"]
        ):
            result = progress["job_result"]
            out = {"job_status": status, "job_result": result}
            if result.get("results") and len(result["results"]) > 1:
                out["best_result"] = result.get("best_result")
            if result.get("failed_subtasks"):
                out["failed_subtasks"] = result["failed_subtasks"]
            return out
        return progress

    def stream_status(self, sid: str, job_id: str, tick_s: Optional[float] = None):
        """Generator yielding progress dicts until completion — the SSE body
        (master.py:237-266 semantics, 1.5 s default tick). Between progress
        snapshots, freshly-ingested learning curves are interleaved as
        ``{"kind": "curve", ...}`` events (incremental: the store's version
        counter is the cursor, so each curve streams exactly once). The
        progress snapshot is read BEFORE the curve drain: a terminal
        status implies aggregation finished, so every curve ingested
        before it is already behind the cursor and flushes on this final
        iteration — nothing is lost to the return."""
        tick = tick_s if tick_s is not None else self.config.service.sse_tick_s
        since = 0
        while True:
            progress = self.store.job_progress(sid, job_id)
            fresh, since = self.curves.updates(job_id, since)
            for entry in fresh:
                yield {"kind": "curve", "job_id": job_id, **entry}
            yield progress
            if progress["job_status"] in TERMINAL_STATUSES:
                return
            time.sleep(tick)

    def job_metrics(self, sid: str, job_id: str) -> List[Dict[str, Any]]:
        """Per-subtask results array (the reference's /metrics endpoint
        replays the Kafka metrics topic, master.py:294-340; here it's a
        store read — same payload, no broker rewind). Snapshots to
        metrics.json like the reference (master.py:336-337)."""
        self._require_session(sid)
        results = self.store.subtask_results(sid, job_id)
        try:
            import json
            import os

            os.makedirs(self.config.storage.root, exist_ok=True)
            with open(os.path.join(self.config.storage.root, "metrics.json"), "w") as f:
                json.dump(json_safe(results), f, indent=2)
        except OSError:
            logger.exception("metrics.json snapshot failed")
        return results

    def job_cost(self, job_id: str) -> Optional[Dict[str, Any]]:
        """Hardware-grounded cost report for a job: device-seconds, total
        model/XLA FLOPs and bytes, HBM high-water, and per-group MFU —
        aggregated from the ``batch_cost`` records the executors stamp onto
        each batch's primary result (runtime/executor._record_batch_cost).
        None when the job id is unknown; a known job with no cost records
        (CS230_OBS=0, or a run predating the accounting layer) reports
        zeros with an empty ``groups`` list. Schema:
        docs/OBSERVABILITY.md "Job cost report"."""
        sid = next(
            (
                j["session_id"]
                for j in self.store.jobs_overview()
                if j["job_id"] == job_id
            ),
            None,
        )
        if sid is None:
            return None
        from ..utils.backend import device_peak_flops

        progress = self.store.job_progress(sid, job_id)
        groups: List[Dict[str, Any]] = []
        device_seconds = 0.0
        capacity_device_seconds = 0.0  # device_seconds x participating devices
        model_flops = 0.0
        xla_flops = 0.0
        bytes_accessed = 0.0
        hbm_peak = None
        priced = True  # every group carries a model-FLOP figure
        for r in self.store.subtask_results(sid, job_id):
            cost = (r or {}).get("batch_cost")
            if not cost:
                continue
            groups.append(dict(cost))
            device_seconds += float(cost.get("device_seconds") or 0.0)
            capacity_device_seconds += float(
                cost.get("device_seconds") or 0.0
            ) * max(int(cost.get("n_devices") or 1), 1)
            # a group counts as priced only with a COMPLETE model-FLOP sum
            # (flops_coverage 1.0) — job MFU from partial sums would
            # understate utilization and read as a real figure
            if (
                cost.get("model_flops") is not None
                and cost.get("flops_coverage") == 1.0
            ):
                model_flops += float(cost["model_flops"])
            else:
                priced = False
            if cost.get("xla_flops") is not None:
                xla_flops += float(cost["xla_flops"])
            if cost.get("bytes_accessed") is not None:
                bytes_accessed += float(cost["bytes_accessed"])
            if cost.get("hbm_peak_bytes") is not None:
                hbm_peak = max(hbm_peak or 0, int(cost["hbm_peak_bytes"]))
        peak = device_peak_flops()
        mfu = None
        if peak and capacity_device_seconds > 0 and model_flops > 0 and priced:
            # capacity-weighted: each group's window counts once per
            # participating device, so mesh batches don't inflate MFU
            mfu = model_flops / (capacity_device_seconds * peak)
        return {
            "job_id": job_id,
            "session_id": sid,
            "job_status": progress.get("job_status"),
            "n_groups": len(groups),
            "device_seconds": device_seconds,
            "model_flops": model_flops if groups and priced else None,
            "xla_flops": xla_flops if xla_flops > 0 else None,
            "bytes_accessed": bytes_accessed if bytes_accessed > 0 else None,
            "hbm_peak_bytes": hbm_peak,
            # MFU is null off-accelerator (device_peak_flops() is None on
            # CPU — utilization of a host backend is not a meaningful
            # number) and whenever any group lacks a model-FLOP estimate
            "mfu": mfu,
            "device_peak_flops": peak,
            "groups": groups,
        }

    def critical_path(self, job_id: str) -> Optional[Dict[str, Any]]:
        """Exact wall-clock decomposition of one job (obs/critpath.py):
        the span tree joined with the flight-recorder timelines, tiled
        into labeled critical-path segments that sum to the measured
        wall (gaps labeled ``untraced``). None when no trace is bound to
        the job — the ``GET /critical_path`` 404. Schema:
        docs/OBSERVABILITY.md "Critical path & trace export"."""
        from ..obs.critpath import critical_path as _critical_path

        tid = TRACER.trace_for_job(job_id)
        if tid is None:
            return None
        timelines = {
            stid: RECORDER.timeline(job_id, stid) or []
            for stid in RECORDER.job_subtasks(job_id)
        }
        # the store-measured wall (created_at -> completion_time), when
        # the job record still exists, cross-checks the span window
        job_wall = None
        sid = next(
            (
                j["session_id"]
                for j in self.store.jobs_overview()
                if j["job_id"] == job_id
            ),
            None,
        )
        if sid is not None:
            try:
                job = self.store.get_job(sid, job_id)
                if job.get("completion_time") and job.get("created_at"):
                    job_wall = float(job["completion_time"]) - float(
                        job["created_at"]
                    )
            except KeyError:
                pass
        return _critical_path(
            job_id,
            trace_id=tid,
            spans=TRACER.spans_for(tid),
            timelines=timelines,
            job_wall_s=job_wall,
        )

    def explain(self, job_id: str, subtask_id: str) -> Dict[str, Any]:
        """Flight-recorder timeline for one subtask — every lifecycle
        decision in order (placement with score breakdown, lease grant/
        reclaim, attempts, retries, speculation, terminal result /
        quarantine). Raises KeyError when the recorder never saw the pair
        (unknown ids, a run under ``CS230_OBS=0``, or a timeline already
        evicted from the bounded ring) — the ``GET /explain`` 404. Schema:
        docs/OBSERVABILITY.md "Flight recorder"."""
        timeline = RECORDER.timeline(job_id, subtask_id)
        if timeline is None:
            raise KeyError(
                f"no recorded events for subtask {subtask_id!r} of job "
                f"{job_id!r}"
            )
        return {
            "job_id": job_id,
            "subtask_id": subtask_id,
            "n_events": len(timeline),
            "events": timeline,
        }

    def prewarm_hints(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Prewarm hints for a freshly-registered worker: the most recent
        job shape per (model family, dataset), ranked by the runtime
        predictor's hot families (``PlacementEngine.hot_families`` — the
        families the fleet has actually been running), newest-first within
        a rank. Shipped in the ``POST /subscribe`` response so the agent's
        background prewarm (runtime/prewarm.py) can load those
        executables and stage those datasets BEFORE the first placement
        arrives. Empty when ``CS230_PREWARM=0`` or nothing has run yet."""
        from .prewarm import enabled as prewarm_enabled
        from .prewarm import max_hints

        if not prewarm_enabled():
            return []
        if self.overload_shedding():
            # graceful degradation: an overloaded fleet must not spend
            # idle-window device time warming SPECULATIVE shapes — shed
            # prewarm before admission starts rejecting real submits
            counter_inc("tpuml_overload_shed_total", kind="prewarm")
            return []
        limit = limit if limit is not None else max_hints()
        if limit <= 0:
            return []
        hints: Dict[Any, Dict[str, Any]] = {}
        # jobs_overview is newest-first: the first job seen per
        # (family, dataset) is the most recent shape of that family.
        # hint_shape extracts one param dict + scalar train_params per
        # selected job — NOT the get_job deep copy, which would serialize
        # every subtask spec/result of thousand-trial jobs under the
        # store lock on every /subscribe (agent restarts re-register
        # routinely under the fault-tolerance layer)
        for job in self.store.jobs_overview():
            family, dataset_id = job.get("model_type"), job.get("dataset_id")
            if not family or not dataset_id or (family, dataset_id) in hints:
                continue
            try:
                shape = self.store.hint_shape(
                    job["session_id"], job["job_id"]
                )
            except Exception:  # noqa: BLE001 — evicted/foreign job
                continue
            hints[(family, dataset_id)] = {
                "model_type": family,
                "dataset_id": dataset_id,
                **shape,
            }
        ranked = list(hints.values())
        hot = (
            self.cluster.engine.hot_families(top_n=max(limit, 5))
            if self.cluster is not None
            else []
        )
        rank = {family: i for i, family in enumerate(hot)}
        ranked.sort(key=lambda h: rank.get(h["model_type"], len(rank)))
        return ranked[:limit]

    def predictor_calibration(self) -> Dict[str, Any]:
        """Per-model-family predicted-vs-actual calibration of the runtime
        predictor driving placement/lease decisions — the
        ``GET /predictor/calibration`` body. Empty ``families`` in direct
        mode (no placement engine ran, nothing was predicted)."""
        families: Dict[str, Any] = {}
        if self.cluster is not None:
            report = getattr(
                self.cluster.engine.predictor, "calibration_report", None
            )
            if report is not None:
                families = report()
        return {"families": families, "n_families": len(families)}

    def wait_for_completion(self, sid: str, job_id: str, timeout_s: Optional[float] = None) -> Dict[str, Any]:
        timeout = timeout_s or self.config.service.client_timeout_s
        if not self.store.wait_job(sid, job_id, timeout):
            raise TimeoutError(f"Job {job_id} did not complete in time")
        return self.store.job_progress(sid, job_id)

    def best_model_path(self, sid: str, job_id: str) -> Optional[str]:
        self._require_session(sid)
        job = self.store.get_job(sid, job_id)
        result = job.get("result") or {}
        best = result.get("best_result") or {}
        if best.get("model_path"):
            return best["model_path"]
        with self._artifact_lock:
            path = self._artifact_paths.get((sid, job_id))
            if path is not None:
                return path
            st = self._artifact_specs.get((sid, job_id))
        if st is None:
            return None
        artifact = self.executor.fit_artifact(st)
        path = save_artifact(st["subtask_id"], artifact, self.config.storage.models_dir)
        with self._artifact_lock:
            self._artifact_paths[(sid, job_id)] = path
        return path

    def _require_session(self, sid: str) -> None:
        if not self.store.has_session(sid):
            raise KeyError(f"Invalid session id: {sid}")
