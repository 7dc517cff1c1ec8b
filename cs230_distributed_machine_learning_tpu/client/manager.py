"""MLTaskManager: the user-facing client API.

Method-for-method parity with the reference SDK
(``DistributedLibrary/src/distributed_ml/core.py:15-213``): sessions are
created at construction; ``check_data`` / ``download_data`` / ``preprocess``
manage datasets; ``train`` accepts a live sklearn estimator or
GridSearchCV/RandomizedSearchCV wrapper plus ``train_params`` and optionally
blocks with progress; ``check_job_status`` returns per-trial metrics;
``download_best_model`` fetches the winning artifact.

Two transports:
- **local** (default, ``url=None``): talks directly to an in-process
  Coordinator — the idiomatic single-host TPU deployment (no HTTP at all).
- **remote** (``url=...``): REST against a coordinator server
  (runtime/server.py), wire-compatible with the reference master's routes.

Reference client quirks fixed, not copied (SURVEY.md §2.1): the broken
status-code check (core.py:31), train() posting to the SSE endpoint but
polling /metrics (core.py:169,178), and the 60 s default timeout.
"""

from __future__ import annotations

import json
import random
import time
import uuid
from typing import Any, Dict, Optional

from ..obs import TRACE_HEADER, activate, new_trace_id, span
from ..runtime.store import TERMINAL_STATUSES
from ..utils.config import get_config
from ..utils.serialization import json_safe
from .introspection import extract_model_details


class MLTaskManager:
    def __init__(
        self,
        url: Optional[str] = None,
        coordinator=None,
        priority: int = 0,
    ):
        """``priority`` is this session's QoS lane (docs/ARCHITECTURE.md
        "QoS priority lanes"): subtasks of its jobs dispatch ahead of
        lower lanes when the fleet is backlogged. Default 0 keeps the
        legacy FIFO behavior."""
        self.api_url = url.rstrip("/") if url else None
        self.priority = int(priority)
        if self.api_url is None:
            if coordinator is None:
                from ..runtime.coordinator import Coordinator

                coordinator = Coordinator()
            self._coordinator = coordinator
        else:
            self._coordinator = None
        self.session_id = self._create_session()
        self.job_id: Optional[str] = None
        self.result: Optional[Dict[str, Any]] = None
        #: trace id of the most recent train() — minted client-side and
        #: propagated to the coordinator (X-Trace-Id header on REST, trace
        #: context in local mode); read GET /trace/<job_id> with it
        self.trace_id: Optional[str] = None

    # ------------- session -------------

    def _create_session(self) -> str:
        if self._coordinator is not None:
            return self._coordinator.create_session(
                priority=self.priority
            )
        resp = self._request(
            "post", "create_session",
            json={"priority": self.priority} if self.priority else None,
        )
        return resp["session_id"]

    # ------------- data management -------------

    def check_data(self, data_name: str) -> Dict[str, Any]:
        if self._coordinator is not None:
            return self._coordinator.check_data(self.session_id, data_name)
        return self._request(
            "get", f"check_data/{self.session_id}", params={"dataset_name": data_name}
        )

    def download_data(self, data_link: str, data_name: str, data_type: str) -> Dict[str, Any]:
        if self._coordinator is not None:
            return self._coordinator.download_data(
                self.session_id, data_link, data_name, data_type
            )
        return self._request(
            "post",
            f"download_data/{self.session_id}",
            json={
                "dataset_url": data_link,
                "dataset_name": data_name,
                "dataset_type": data_type,
            },
        )

    def preprocess(self, dataset_id: str, config: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        if self._coordinator is not None:
            return self._coordinator.preprocess(self.session_id, dataset_id, config)
        return self._request(
            "post",
            f"preprocess/{self.session_id}",
            json={"dataset_id": dataset_id, "config": config},
        )

    # ------------- training -------------

    def train(
        self,
        estimator: Any,
        dataset_id: Optional[str] = None,
        train_params: Optional[Dict[str, Any]] = None,
        wait_for_completion: bool = True,
        timeout: Optional[float] = None,
        show_progress: bool = True,
        *,
        dataset_name: Optional[str] = None,
        stream: bool = False,
        search_params: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Submit a training / hyperparameter-search job.

        train_params: {test_size=0.2, random_state=42, cv=5} — the plain-
        estimator default test_size matches the reference (core.py:160-163).
        ``dataset_name=`` is accepted as an alias for ``dataset_id`` — the
        reference README's examples use that keyword (README.md:70-76).

        ``search_params=`` opts the job into adaptive search
        (docs/SEARCH.md): ``{"type": "asha" | "hyperband", "eta": 3,
        "min_resource": r, "max_resource": R, "n_iter": n,
        "stop_score": s, "max_brackets": b}``. The estimator's
        param grid/distributions supply the trial configurations (a
        RandomizedSearchCV wrapper works as-is); the rung controller owns
        the resource knob (max_iter / n_estimators) and stops doomed
        trials early with the ``pruned`` terminal status. Progress events
        then carry ``tasks_pruned`` and a per-rung ``search`` summary.

        ``stream=True`` (with ``wait_for_completion``) follows the job by
        CONSUMING the server-sent-event stream instead of polling: remote
        mode posts to ``/train_status`` and reads its SSE body (the
        reference client posted there and then ignored the stream,
        core.py:169 — fixed, not copied); local mode consumes the
        coordinator's ``stream_status`` generator. Progress events update
        the progress bar; the final event carries ``job_result``.
        """
        if dataset_name is not None:
            if dataset_id is not None and dataset_id != dataset_name:
                raise TypeError(
                    f"conflicting dataset_id={dataset_id!r} and "
                    f"dataset_name={dataset_name!r} — pass one"
                )
            dataset_id = dataset_name
        if dataset_id is None:
            raise TypeError("train() requires a dataset id (dataset_id= or dataset_name=)")
        model_details = extract_model_details(estimator)
        if search_params:
            sp = dict(search_params)
            stype = sp.pop("type", "asha")
            if stype not in ("asha", "hyperband"):
                raise ValueError(
                    f"search_params['type'] must be 'asha' or 'hyperband', "
                    f"got {stype!r}"
                )
            model_details["search_type"] = stype
            for key in ("n_iter", "random_state"):
                if key in sp:
                    model_details[key] = sp.pop(key)
            model_details["asha"] = sp
        train_params = dict(train_params or {})
        train_params.setdefault("test_size", get_config().execution.default_test_size)
        self.job_id = str(uuid.uuid4())
        payload = {
            "job_id": self.job_id,
            "session_id": self.session_id,
            "dataset_id": dataset_id,
            "model_details": model_details,
            "train_params": train_params,
            "timestamp": time.time(),
        }
        self.trace_id = new_trace_id()
        if self._coordinator is not None:
            # local mode: the job trace starts here — activate the id so
            # submit_train (same process) adopts it. ``client.train`` covers
            # the whole call, what the user waits for: the submit and the
            # wait through the terminal status and the result read
            with activate(self.trace_id), span(
                "client.train", trace_id=self.trace_id,
                job_id=self.job_id, dataset_id=dataset_id,
            ):
                with span("client.submit", job_id=self.job_id):
                    submit = self._coordinator.submit_train(
                        self.session_id, payload
                    )
                self.job_id = submit.get("job_id") or self.job_id
                if not wait_for_completion:
                    return submit
                with span("client.wait", job_id=self.job_id):
                    if stream:
                        return self._stream_local(
                            timeout=timeout, show_progress=show_progress
                        )
                    return self._wait_for_completion(
                        timeout=timeout, show_progress=show_progress
                    )
        else:
            scoring = (model_details.get("cv_params") or {}).get("scoring")
            if callable(scoring) and not isinstance(scoring, str):
                # json_safe would stringify the function into an
                # unsupported-scorer name server-side — fail HERE with the
                # real reason instead (callables work in local mode, where
                # the object reaches the executor's host-side fallback)
                raise ValueError(
                    "callable scoring cannot be sent over the REST "
                    "transport (it is not JSON-serializable); use a scorer "
                    "name, or a local-mode MLTaskManager for callable "
                    "scorers"
                )
            if stream and wait_for_completion:
                # /train_status both submits AND streams: one request
                return self._train_stream(
                    payload, timeout=timeout, show_progress=show_progress
                )
            # idempotent: the payload carries the client-minted job_id and
            # the coordinator dedupes resubmits on it, so a retried POST
            # (coordinator restart, 429 backoff) can never double-expand
            submit = self._request(
                "post", f"train/{self.session_id}", json=json_safe(payload),
                headers={TRACE_HEADER: self.trace_id}, idempotent=True,
            )
        # adopt the CANONICAL job id: a sharded coordinator stamps the
        # client-minted id with its shard (``s<k>-``) so any front end
        # routes follow-up status/SSE/model requests without a lookup
        # (runtime/sharding.py); unsharded coordinators echo the id back
        self.job_id = submit.get("job_id") or self.job_id
        if not wait_for_completion:
            return submit
        return self._wait_for_completion(timeout=timeout, show_progress=show_progress)

    def _wait_for_completion(
        self, timeout: Optional[float] = None, show_progress: bool = True
    ) -> Dict[str, Any]:
        cfg = get_config().service
        timeout = timeout or cfg.client_timeout_s
        poll = cfg.client_poll_s if self._coordinator is None else 0.1
        bar = self._progress_bar(show_progress)
        deadline = time.time() + timeout
        try:
            while time.time() < deadline:
                if self._coordinator is not None:
                    # event-driven: wake on finalize (or after `poll` to
                    # refresh the progress bar), never a blind sleep
                    self._coordinator.store.wait_job(
                        self.session_id,
                        self.job_id,
                        timeout=(min(poll, deadline - time.time()) if bar is not None
                                 else deadline - time.time()),
                    )
                status = self.check_status()
                job_status = status.get("job_status")
                if bar is not None:
                    bar.n = int(_pct(job_status))
                    _bar_postfix(bar, status)
                    bar.refresh()
                if job_status in TERMINAL_STATUSES:
                    self.result = status.get("job_result")
                    return status
                if self._coordinator is None:
                    time.sleep(poll)
        finally:
            if bar is not None:
                bar.close()
        raise TimeoutError(f"Job {self.job_id} did not complete within {timeout}s")

    # ------------- SSE streaming (stream=True) -------------

    @staticmethod
    def _progress_bar(show_progress: bool):
        if not show_progress:
            return None
        try:
            from tqdm import tqdm

            # disable=None: auto-off when stderr is not a tty (piped
            # logs otherwise get one bar line per poll tick)
            return tqdm(total=100, desc="job", unit="%", disable=None)
        except ImportError:
            return None

    def _finish_stream(self, last: Optional[Dict[str, Any]], timeout: float):
        if last is None or last.get("job_status") not in TERMINAL_STATUSES:
            raise TimeoutError(
                f"Job {self.job_id} stream ended without completion "
                f"(timeout {timeout}s)"
            )
        self.result = last.get("job_result")
        return last

    def _stream_local(
        self, timeout: Optional[float] = None, show_progress: bool = True
    ) -> Dict[str, Any]:
        """Local-mode stream consumption: iterate the coordinator's
        ``stream_status`` generator (the SSE body source) to completion."""
        timeout = timeout or get_config().service.client_timeout_s
        deadline = time.time() + timeout
        bar = self._progress_bar(show_progress)
        last: Optional[Dict[str, Any]] = None
        try:
            for progress in self._coordinator.stream_status(
                self.session_id, self.job_id
            ):
                if progress.get("kind") == "curve":
                    # interleaved learning-curve event (trial telemetry
                    # plane) — not a progress snapshot; read via curves()
                    continue
                last = progress
                if bar is not None:
                    bar.n = int(_pct(progress.get("job_status")))
                    _bar_postfix(bar, progress)
                    bar.refresh()
                if progress.get("job_status") in TERMINAL_STATUSES:
                    break
                if time.time() > deadline:
                    raise TimeoutError(
                        f"Job {self.job_id} did not complete within {timeout}s"
                    )
        finally:
            if bar is not None:
                bar.close()
        return self._finish_stream(last, timeout)

    def _train_stream(
        self,
        payload: Dict[str, Any],
        timeout: Optional[float] = None,
        show_progress: bool = True,
    ) -> Dict[str, Any]:
        """Remote-mode stream consumption: POST the job to ``/train_status``
        and read the SSE events off the response body (one request submits
        and follows). Events arrive every ``sse_tick_s``; a read stalled
        well past that cadence — or the overall deadline — raises.

        A DROPPED stream (coordinator restart, broken connection) is
        resumed, not raised: the payload carries the client-minted job_id
        and the coordinator dedupes resubmits on it, so re-POSTing the same
        body re-attaches to the SAME job's stream and progress continues
        from the last seen event (each SSE event is a full progress
        snapshot — nothing between drop and resume is lost). 429/503
        responses back off per their ``Retry-After``."""
        import requests

        cfg = get_config().service
        timeout = timeout or cfg.client_timeout_s
        start = time.time()
        deadline = start + timeout
        retry_window = max(cfg.request_retry_s, 0.0)
        read_timeout = max(10.0, 8 * cfg.sse_tick_s)
        bar = self._progress_bar(show_progress)
        last: Optional[Dict[str, Any]] = None
        attempt = 0
        established = False  # a stream was successfully opened at least once
        try:
            while time.time() < deadline:
                try:
                    resp = requests.post(
                        f"{self.api_url}/train_status/{self.session_id}",
                        json=json_safe(payload),
                        headers={TRACE_HEADER: self.trace_id}
                        if self.trace_id else None,
                        stream=True,
                        timeout=(10, read_timeout),
                    )
                except (requests.ConnectionError, requests.Timeout):
                    # an endpoint that NEVER answered is a config error,
                    # not a drop: surface it within the retry window
                    # instead of spinning to the job deadline
                    # (request_retry_s=0 restores raise-immediately)
                    if not established and time.time() - start > retry_window:
                        raise
                    attempt += 1
                    time.sleep(_retry_delay(attempt))
                    continue
                if resp.status_code in (429, 503) and retry_window > 0:
                    retry_after = resp.headers.get("Retry-After")
                    resp.close()
                    attempt += 1
                    time.sleep(_retry_delay(attempt, retry_after))
                    continue
                try:
                    # fatal HTTP errors (bad session/payload) raise NOW —
                    # only drops of an ESTABLISHED stream are resumed
                    resp.raise_for_status()
                except requests.HTTPError:
                    resp.close()
                    raise
                established = True
                try:
                    for raw in resp.iter_lines():
                        if not raw:
                            continue
                        line = raw.decode() if isinstance(raw, bytes) else raw
                        if not line.startswith("data: "):
                            continue
                        try:
                            event = json.loads(line[len("data: "):])
                        except ValueError:
                            # a torn event (connection died mid-write):
                            # the stream is about to end — resume path
                            continue
                        if event.get("kind") == "curve":
                            # interleaved learning-curve SSE event — skip
                            # (progress bars want snapshots; curves())
                            attempt = 0
                            continue
                        last = event
                        attempt = 0  # real progress resets the backoff
                        # progress events carry the canonical (shard-
                        # stamped) job id — adopt it so post-stream
                        # status/model calls route through any front end
                        if event.get("job_id"):
                            self.job_id = event["job_id"]
                        if bar is not None:
                            bar.n = int(_pct(event.get("job_status")))
                            _bar_postfix(bar, event)
                            bar.refresh()
                        if event.get("job_status") in TERMINAL_STATUSES:
                            return self._finish_stream(last, timeout)
                        if time.time() > deadline:
                            raise TimeoutError(
                                f"Job {self.job_id} did not complete "
                                f"within {timeout}s"
                            )
                except requests.RequestException:
                    # stream dropped mid-job: resume by re-POSTing the
                    # deduped submit instead of raising (the loop)
                    attempt += 1
                    time.sleep(_retry_delay(attempt))
                finally:
                    resp.close()
                # a stream that ENDED without a terminal event (graceful
                # server shutdown mid-job) resumes exactly like a drop —
                # paced at the SSE tick so a flapping server isn't hammered
                time.sleep(min(1.0, max(cfg.sse_tick_s, 0.1)))
            return self._finish_stream(last, timeout)
        finally:
            if bar is not None:
                bar.close()

    # ------------- status / results -------------

    def check_status(self, job_id: Optional[str] = None) -> Dict[str, Any]:
        jid = job_id or self.job_id
        if self._coordinator is not None:
            return self._coordinator.check_status(self.session_id, jid)
        return self._request("get", f"check_status/{self.session_id}/{jid}")

    def check_job_status(self, job_id: Optional[str] = None):
        """Per-trial metrics array (the reference binds this to /metrics,
        core.py:176-178 — kept for API parity)."""
        jid = job_id or self.job_id
        if self._coordinator is not None:
            return self._coordinator.job_metrics(self.session_id, jid)
        return self._request("get", f"metrics/{self.session_id}/{jid}")

    def explain(
        self, job_id: Optional[str] = None, subtask_id: Optional[str] = None
    ) -> Dict[str, Any]:
        """Flight-recorder timeline for one subtask of a job — every
        scheduling decision in order: placement with its score breakdown,
        lease grant/reclaim, attempts/retries with reasons and backoff,
        speculation, and the terminal result (docs/OBSERVABILITY.md
        "Flight recorder"). ``job_id`` defaults to the latest ``train()``;
        raises KeyError when the coordinator has no recorded events for
        the pair (unknown ids or a run under ``CS230_OBS=0``)."""
        jid = job_id or self.job_id
        if jid is None or subtask_id is None:
            raise TypeError(
                "explain() requires a job id (or a prior train()) and a "
                "subtask_id"
            )
        if self._coordinator is not None:
            return self._coordinator.explain(jid, subtask_id)
        import requests

        try:
            return self._request("get", f"explain/{jid}/{subtask_id}")
        except requests.HTTPError as e:
            if e.response is not None and e.response.status_code == 404:
                # same contract as local mode: absence is a KeyError, not
                # a transport error
                raise KeyError(
                    f"no recorded events for subtask {subtask_id!r} of "
                    f"job {jid!r}"
                ) from e
            raise

    def critical_path(
        self, job_id: Optional[str] = None, compare: Optional[str] = None
    ) -> Dict[str, Any]:
        """Exact wall-clock decomposition of one job: the critical-path
        report (docs/OBSERVABILITY.md "Critical path & trace export") —
        segments that tile submit→aggregate (gaps labeled ``untraced``),
        the dominant segment, and retry/speculation attribution. Pass
        ``compare=<baseline_job_id>`` to attach a per-segment diff
        against another job (``report["diff"]``). ``job_id`` defaults to
        the latest ``train()``; raises KeyError when the coordinator has
        no trace bound for the job (unknown id or ``CS230_OBS=0``)."""
        jid = job_id or self.job_id
        if jid is None:
            raise TypeError(
                "critical_path() requires a job id (or a prior train())"
            )
        if self._coordinator is not None:
            report = self._coordinator.critical_path(jid)
            if report is None:
                raise KeyError(f"no critical path for job {jid!r}")
            if compare is not None:
                from ..obs.critpath import compare as _compare

                base = self._coordinator.critical_path(compare)
                if base is None:
                    raise KeyError(f"no critical path for job {compare!r}")
                report["diff"] = _compare(base, report)
            return report
        import requests

        try:
            return self._request(
                "get", f"critical_path/{jid}",
                params={"compare": compare} if compare is not None else None,
            )
        except requests.HTTPError as e:
            if e.response is not None and e.response.status_code == 404:
                raise KeyError(f"no critical path for job {jid!r}") from e
            raise

    def curves(
        self, job_id: Optional[str] = None, subtask_id: Optional[str] = None
    ) -> Dict[str, Any]:
        """Learning curves captured in-fit for a job — or one trial when
        ``subtask_id`` is given (docs/OBSERVABILITY.md "Trial telemetry
        plane"). Each entry carries the downsampled per-split trace
        (loss / score / grad-norm channels), its rung/attempt, and the
        numerical-health watchdog's ``diverged`` flag. ``job_id``
        defaults to the latest ``train()``; raises KeyError when the
        coordinator has no curves for the pair (unknown ids, or a run
        under ``CS230_CURVES=0`` returns an empty job-level list but a
        404/KeyError for a subtask)."""
        jid = job_id or self.job_id
        if jid is None:
            raise TypeError("curves() requires a job id (or a prior train())")
        if self._coordinator is not None:
            if subtask_id is not None:
                return self._coordinator.subtask_curves(jid, subtask_id)
            out = self._coordinator.job_curves(jid)
            if out is None:
                raise KeyError(f"no job {jid!r}")
            return out
        import requests

        path = f"curves/{jid}" if subtask_id is None else (
            f"curves/{jid}/{subtask_id}"
        )
        try:
            return self._request("get", path)
        except requests.HTTPError as e:
            if e.response is not None and e.response.status_code == 404:
                # same contract as local mode: absence is a KeyError
                raise KeyError(
                    f"no curves for job {jid!r}"
                    + (f" subtask {subtask_id!r}" if subtask_id else "")
                ) from e
            raise

    def best_result(self, job_id: Optional[str] = None) -> Optional[Dict[str, Any]]:
        status = self.check_status(job_id)
        result = status.get("job_result") or {}
        return result.get("best_result")

    def download_best_model(self, job_id: Optional[str] = None, output_path: Optional[str] = None) -> str:
        jid = job_id or self.job_id
        if self._coordinator is not None:
            path = self._coordinator.best_model_path(self.session_id, jid)
            if path is None:
                raise FileNotFoundError("No best model artifact for this job")
            if output_path:
                import shutil

                shutil.copy(path, output_path)
                return output_path
            return path
        out = output_path or f"{jid}_best_model.pkl"
        import requests

        r = requests.get(
            f"{self.api_url}/download_model/{self.session_id}/{jid}", timeout=60
        )
        r.raise_for_status()
        with open(out, "wb") as f:
            f.write(r.content)
        return out

    def load_best_model(self, job_id: Optional[str] = None, as_sklearn: bool = True):
        """Download the winning artifact and load it — by default as a real
        fitted sklearn estimator (state-injected; runtime/sklearn_export.py),
        matching the reference's serve-a-sklearn-pickle contract
        (worker.py:352-356, master.py:270-291). ``as_sklearn=False`` returns
        the raw kernel artifact dict for ``predict_with_artifact``."""
        from ..runtime.artifacts import load_artifact, to_sklearn

        path = self.download_best_model(job_id)
        artifact = load_artifact(path)
        return to_sklearn(artifact) if as_sklearn else artifact

    # ------------- REST plumbing -------------

    def _request(
        self,
        method: str,
        endpoint: str,
        json=None,
        params=None,
        headers=None,
        idempotent: Optional[bool] = None,
    ) -> Dict[str, Any]:
        """One REST call with transport resilience (docs/ROBUSTNESS.md
        "Reconnecting edges"): 429/503 responses are retried after their
        ``Retry-After`` (capped, jittered — the admission-control contract),
        and connection errors are retried with capped jittered exponential
        backoff for IDEMPOTENT requests (GETs by default; ``train`` submits
        opt in because the coordinator dedupes on the client-minted
        job_id). The retry window is ``service.request_retry_s`` (0
        disables — every error raises immediately, the legacy behavior)."""
        import requests

        url = f"{self.api_url}/{endpoint.lstrip('/')}"
        if idempotent is None:
            idempotent = method.lower() == "get"
        retry_window = get_config().service.request_retry_s
        deadline = time.time() + max(retry_window, 0.0)
        attempt = 0
        while True:
            try:
                resp = requests.request(
                    method, url,
                    json=json_safe(json) if json is not None else None,
                    params=params, headers=headers, timeout=600,
                )
            except (requests.ConnectionError, requests.Timeout):
                if not idempotent or time.time() >= deadline:
                    raise
                attempt += 1
                time.sleep(_retry_delay(attempt))
                continue
            if resp.status_code in (429, 503) and time.time() < deadline:
                # the request was NOT processed (admission rejection or a
                # recovering coordinator): safe to retry any method
                attempt += 1
                time.sleep(
                    _retry_delay(attempt, resp.headers.get("Retry-After"))
                )
                continue
            resp.raise_for_status()
            return resp.json()


def _retry_delay(attempt: int, retry_after=None, cap: float = 30.0) -> float:
    """Capped jittered backoff. A server-sent ``Retry-After`` is the
    floor (don't come back sooner), padded with up to 25% jitter so a
    rejected fleet doesn't return in lockstep; otherwise exponential from
    0.5 s with full jitter."""
    if retry_after is not None:
        try:
            # jitter first, cap last — the cap is a real ceiling
            return min(float(retry_after) * (1.0 + 0.25 * random.random()), cap)
        except (TypeError, ValueError):
            pass
    return min(10.0, 0.5 * 2 ** min(attempt - 1, 5)) * (0.5 + random.random())


def _bar_postfix(bar, progress: Dict[str, Any]) -> None:
    """Adaptive-search progress decoration (docs/SEARCH.md): pruned count
    and the highest active rung ride the tqdm postfix so a user watching
    the bar sees the controller working, not just percent-done."""
    pruned = progress.get("tasks_pruned")
    diverged = progress.get("tasks_diverged")
    search = progress.get("search")
    if not pruned and not diverged and not search:
        return
    post = {}
    if pruned:
        post["pruned"] = pruned
    if diverged:
        post["diverged"] = diverged
    if isinstance(search, dict):
        rungs = [
            r
            for b in (search.get("brackets") or [search])
            for r in (b.get("rungs") or [])
            if r.get("reported")
        ]
        if rungs:
            post["rung"] = max(r["rung"] for r in rungs)
    try:
        bar.set_postfix(post, refresh=False)
    except Exception:  # noqa: BLE001 — cosmetic only
        pass


def _pct(job_status) -> float:
    if job_status in ("completed", "completed_with_failures"):
        return 100.0
    if isinstance(job_status, str) and job_status.endswith("%"):
        try:
            return float(job_status[:-1])
        except ValueError:
            return 0.0
    return 0.0
