"""Static-shape cross-validation: splits as weight masks.

The reference worker runs, per subtask, one ``train_test_split`` fit + eval
and a 5-fold ``cross_val_score`` on the full data — i.e. K+1 fits per trial
(``aws-prod/worker/worker.py:302-349``). On TPU, data-dependent subset shapes
would defeat XLA, so every split is expressed as a pair of {0,1} weight
vectors over the *full* (static-shape) dataset:

  row k of ``train_w`` selects the fit subset of split k,
  row k of ``eval_w``  selects the scoring subset of split k,

and kernels use weighted losses/metrics. Because sklearn's regularized
objectives are sums (not means) over samples, 0/1-weighting reproduces
fitting on the subset exactly.

Fold assignment itself is computed host-side with sklearn's own splitters so
fold boundaries (and therefore CV scores and ``best_params_``) match sklearn
bit-for-bit: StratifiedKFold for classifiers, KFold for regressors — the
same defaults ``cross_val_score(cv=5)`` uses.
"""

from __future__ import annotations

import collections
import dataclasses
import numbers
import threading

import numpy as np


@dataclasses.dataclass(frozen=True)
class SplitPlan:
    """K+1 splits over n samples. Split 0 is the train/test holdout split
    (eval = test set); splits 1..K are the CV folds (eval = held-out fold)."""

    train_w: np.ndarray  # [K+1, n] float32 {0,1}
    eval_w: np.ndarray   # [K+1, n] float32 {0,1}
    n_folds: int
    #: (task, n, n_folds, test_size, random_state): what the plan depends
    #: on *besides the labels*. ``y`` is not in it and StratifiedKFold
    #: reads ``y``, so equal signatures mean equal masks for ONE dataset
    #: only: two datasets of equal ``n`` share a signature and differ in
    #: their masks. Every cache keyed on it must therefore prefix the
    #: dataset's content fingerprint, and both do: the trial engine's
    #: device-staging cache (``trial_map._staged_device``) and the host
    #: memo below (``SplitPlanCache``). None (e.g. hand-built test plans)
    #: disables caching.
    signature: tuple | None = None

    @property
    def n_splits(self) -> int:
        return self.train_w.shape[0]

    @property
    def n_samples(self) -> int:
        return self.train_w.shape[1]

    @property
    def fit_rows(self) -> int:
        """The most rows any split trains on."""
        return int(np.count_nonzero(self.train_w > 0, axis=1).max(initial=0))


def plan_signature(
    n: int, *, task: str, n_folds: int, test_size: float, random_state
) -> tuple:
    """``SplitPlan.signature`` from the arguments alone, before anything is
    built: the part of a memo key that is not the dataset."""
    return (task, n, n_folds or 0, float(test_size), random_state)


def build_split_plan(
    y: np.ndarray,
    *,
    task: str,
    n_folds: int = 5,
    test_size: float = 0.2,
    random_state: int | None = 42,
) -> SplitPlan:
    """Build the K+1 split masks for one dataset.

    task: "classification" uses stratified folds + stratify-free holdout,
    "regression" uses plain KFold — matching sklearn's cross_val_score
    defaults and the reference worker's train_test_split usage (with its
    positional-arg bug fixed, see SURVEY.md §2.4).
    """
    from sklearn.model_selection import KFold, StratifiedKFold, train_test_split

    n = len(y)
    idx = np.arange(n)
    train_idx, test_idx = train_test_split(
        idx, test_size=test_size, random_state=random_state
    )

    rows_train = [_mask(n, train_idx)]
    rows_eval = [_mask(n, test_idx)]

    if n_folds and n_folds >= 2:
        if task == "classification":
            splitter = StratifiedKFold(n_splits=n_folds)
            split_iter = splitter.split(np.zeros(n), y)
        else:
            splitter = KFold(n_splits=n_folds)
            split_iter = splitter.split(np.zeros(n))
        for fold_train, fold_eval in split_iter:
            rows_train.append(_mask(n, fold_train))
            rows_eval.append(_mask(n, fold_eval))

    return SplitPlan(
        train_w=np.stack(rows_train).astype(np.float32),
        eval_w=np.stack(rows_eval).astype(np.float32),
        n_folds=n_folds or 0,
        signature=plan_signature(
            n, task=task, n_folds=n_folds, test_size=test_size,
            random_state=random_state,
        ),
    )


def _mask(n: int, idx: np.ndarray) -> np.ndarray:
    m = np.zeros(n, dtype=np.float32)
    m[idx] = 1.0
    return m


#: Host bytes the memo below may hold. A plan is 8 * (n_folds + 1) * n
#: bytes: 240 MB at 5M rows and cv=5, 530 MB at 11M (about what one chip's
#: memory admits), so 1 GiB keeps a session's few plans (its search plan,
#: the holdout-only plan of ``fit_artifact``, a second dataset) on a host
#: that holds the datasets themselves several times over. A constant and
#: not a setting: nothing that runs needs another value, and the newest
#: plan is admitted whatever its size.
SPLIT_PLAN_CACHE_BYTES = 1 << 30


class SplitPlanCache:
    """Process-wide memo of built plans, so that a warm search does not
    run sklearn's splitters over every row again (2.0 s and 240 MB a search
    at 5M rows, PERF.md section 6, PR 27).

    Keyed by content: ``(dataset fingerprint, plan_signature(...))`` (see
    ``SplitPlan.signature`` for why the fingerprint is not optional). A hit
    returns the very plan a miss built, shared by jobs and threads, so its
    masks are read-only. Concurrent misses on one key build once (the stage
    cache's single-flight); LRU under ``max_bytes``."""

    def __init__(self, max_bytes: int = SPLIT_PLAN_CACHE_BYTES):
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._plans: "collections.OrderedDict[tuple, SplitPlan]" = (
            collections.OrderedDict()
        )
        #: key -> Event of a build in flight
        self._inflight: dict = {}
        self._bytes = 0

    def get_or_build(
        self,
        fingerprint: str,
        y: np.ndarray,
        *,
        task: str,
        n_folds: int,
        test_size: float,
        random_state: int | None,
    ) -> tuple[SplitPlan, str]:
        """``(plan, outcome)``: ``hit`` (memoised, possibly after waiting
        for another thread's build), ``miss`` (this caller built it) or
        ``bypass`` (``random_state`` is not an integer seed: None asks for
        a fresh draw and a RandomState instance advances with every use,
        so neither is ever memoised)."""
        kw = dict(
            task=task, n_folds=n_folds, test_size=test_size,
            random_state=random_state,
        )
        if not isinstance(random_state, numbers.Integral):
            return build_split_plan(y, **kw), "bypass"
        key = (fingerprint, plan_signature(len(y), **kw))
        while True:
            with self._lock:
                plan = self._plans.get(key)
                if plan is not None:
                    self._plans.move_to_end(key)
                    return plan, "hit"
                ev = self._inflight.get(key)
                if ev is None:
                    ev = self._inflight[key] = threading.Event()
                    break
            ev.wait()
        try:
            plan = build_split_plan(y, **kw)
            plan.train_w.setflags(write=False)
            plan.eval_w.setflags(write=False)
            with self._lock:
                self._plans[key] = plan
                self._bytes += _plan_nbytes(plan)
                while self._bytes > self.max_bytes and len(self._plans) > 1:
                    _, old = self._plans.popitem(last=False)
                    self._bytes -= _plan_nbytes(old)
        finally:
            # after the insert, so a waiter finds the plan; after a failed
            # build, so the next waiter becomes the builder
            with self._lock:
                del self._inflight[key]
            ev.set()
        return plan, "miss"

    def nbytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def clear(self) -> None:
        """Drop every plan (tests)."""
        with self._lock:
            self._plans.clear()
            self._bytes = 0


def _plan_nbytes(plan: SplitPlan) -> int:
    return plan.train_w.nbytes + plan.eval_w.nbytes


#: the memo every executor of the process shares
SPLIT_PLAN_CACHE = SplitPlanCache()
