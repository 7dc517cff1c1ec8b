"""The comparison that decides ``correct``.

It looks at what the timed searches returned, nothing else of the program:
the parameters each trial was given, every trial's per-split scores, the
mean the coordinator aggregated and the best entry it chose. Expansion is
checked against what the search kind's own file draws from the seed
(``searches/<kind>.py``); scores of a sample of
trials drawn from the seed (all their splits) against the family's plain
reference on the same rows and splits; the aggregation against arithmetic
on the program's own per-split scores. It looks at no winner, rank or
argmax by identity: the best entry is held to the largest mean, not to an
index, so a near-tie cannot flip it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np


def split_masks(y: np.ndarray, cv: int, test_size: float, random_state: int):
    """K+1 (train, eval) 0/1 masks as the system documents them: split 0 the
    ``train_test_split`` holdout, splits 1..K sklearn's StratifiedKFold."""
    from sklearn.model_selection import StratifiedKFold, train_test_split

    n = len(y)
    tr, te = train_test_split(np.arange(n), test_size=test_size, random_state=random_state)
    pairs = [(tr, te)] + list(StratifiedKFold(n_splits=cv).split(np.zeros(n), y))
    TW = np.zeros((len(pairs), n), np.uint8)
    EW = np.zeros((len(pairs), n), np.uint8)
    for s, (a, b) in enumerate(pairs):
        TW[s, a] = 1
        EW[s, b] = 1
    return TW, EW


def sample_trials(n_iter: int, n_check: int, seed: int, combos=None, sensitive=None) -> List[int]:
    """The trials whose answers are compared, drawn from the seed. Where the
    configuration names ``sensitive_trials`` (the ``lowest`` k by a
    ``parameter``), those come first and the rest of the sample is drawn
    from the others."""
    first: List[int] = []
    if sensitive:
        order = sorted(range(n_iter), key=lambda i: (float(combos[i][sensitive["parameter"]]), i))
        first = order[: int(sensitive["lowest"])]
    rest = [i for i in range(n_iter) if i not in first]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x5EED])
    drawn = rng.choice(len(rest), size=max(0, min(n_check, n_iter) - len(first)), replace=False)
    return first + sorted(rest[int(j)] for j in drawn)


def _by_index(results: Sequence[Dict[str, Any]]) -> Dict[int, Dict[str, Any]]:
    out = {}
    for r in results:
        try:
            out[int(str(r.get("subtask_id", "")).rsplit("-", 1)[1])] = r
        except (IndexError, ValueError):
            pass
    return out


def split_scores(result: Dict[str, Any]) -> List[float]:
    return [float(result["accuracy"])] + [float(v) for v in result["cv_scores"]]


def count_failed(status: Dict[str, Any], n_iter: int) -> int:
    """Trials of one search that did not come back sound: a job that did not
    complete loses all of them; a failed, missing or non-finite trial one."""
    if status.get("job_status") != "completed":
        return n_iter
    res = status.get("job_result") or {}
    got = _by_index(res.get("results") or [])
    ok = sum(1 for i in range(n_iter)
             if i in got and got[i].get("status") == "completed"
             and "cv_scores" in got[i] and np.isfinite(got[i]["mean_cv_score"]))
    return n_iter - ok


def curve_rows(result: Dict[str, Any], steps: int):
    """The recorded ``gmax`` curve of one trial, [n_splits, slots], and the
    solver step each slot holds (the last of its stride window)."""
    rec = result.get("curve") or {}
    rows = rec.get("gmax")
    if not rows:
        return None, None
    stride = int(rec.get("stride", 1))
    a = np.asarray([[np.nan if v is None else v for v in row] for row in rows], np.float64)
    at = np.minimum(stride * (np.arange(a.shape[1]) + 1) - 1, steps - 1)
    return a, at


def curve_gap(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative gap of ``gmax`` curves, slot by slot; a slot that is missing
    or not finite is infinitely far."""
    want = want.astype(np.float64)
    g = np.abs(got - want) / np.maximum(want, 1e-30)
    return np.where(np.isfinite(g), g, np.inf)


def compare(cell: Dict[str, Any], combos: Sequence[Dict[str, Any]], seed: int, X, y,
            searches: Sequence[Dict[str, Any]], reference) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """Numbers compared, by name, for the searches of one run; the caller
    holds each against its limit. ``combos`` are the parameters the search
    kind draws from the seed; ``detail`` carries per-trial gaps."""
    config, traffic = cell["config"], cell["traffic"]
    n_iter, cv = int(traffic["n_iter"]), int(traffic["cv"])
    fixed = dict(config["estimator"]["params"])
    sensitive = config.get("sensitive_trials")
    picked = sample_trials(n_iter, int(traffic["check_trials"]), seed, combos, sensitive)
    params = [{**fixed, **combos[i]} for i in picked]
    splits = split_masks(np.asarray(y), cv, float(traffic["test_size"]),
                         int(traffic["split_random_state"]))
    n_classes = int(config["dataset"]["n_classes"])
    ref_out = reference(X, y, n_classes, params, splits)
    ref = ref_out["score"]  # [len(picked), cv + 1]
    ref_gmax = ref_out.get("gmax")  # [len(picked), cv + 1, steps] or None

    mismatch, mean_gap, best_gap = 0, 0.0, 0.0
    gaps, curve_gaps, curve_at = [], [], None
    for status in searches:
        res = status.get("job_result") or {}
        got = _by_index(res.get("results") or [])
        means = []
        for i in range(n_iter):
            r = got.get(i)
            if r is None or "cv_scores" not in r:
                mismatch += 1
                continue
            want = {**fixed, **combos[i]}
            p = r.get("parameters") or {}
            if any(k not in p or (list(p[k]) != list(v) if isinstance(v, (list, tuple))
                                  else p[k] != v) for k, v in want.items()):
                mismatch += 1
            means.append(float(r["mean_cv_score"]))
            mean_gap = max(mean_gap, abs(float(r["mean_cv_score"]) - float(np.mean(r["cv_scores"]))))
        best = res.get("best_result") or {}
        best_gap = max(best_gap, abs(float(best.get("mean_cv_score", np.inf)) - max(means))
                       if means else np.inf)
        rows = [split_scores(got[i]) if i in got and "cv_scores" in got[i]
                else [np.nan] * (cv + 1) for i in picked]
        gaps.append(np.abs(np.asarray(rows, np.float64) - ref.astype(np.float64)))
        if ref_gmax is not None:
            for j, i in enumerate(picked):
                got_c, at = curve_rows(got.get(i) or {}, ref_gmax.shape[2])
                if got_c is None or got_c.shape != (ref_gmax.shape[1], len(at)):
                    curve_gaps.append(np.full((1, 1), np.inf))
                    continue
                curve_at = at
                curve_gaps.append(curve_gap(got_c, ref_gmax[j][:, at]))
    g = np.stack(gaps) if gaps else np.full((1, 1, 1), np.inf)  # [searches, trials, splits]
    g = np.where(np.isfinite(g), g, np.inf)
    trial_gap = g.mean(axis=(0, 2))
    numbers = {
        "params_mismatch": float(mismatch),
        "mean_gap": float(mean_gap),
        "best_gap": float(best_gap),
        "score_gap_max": float(g.max()),
        "score_gap_mean": float(g.mean()),
    }
    detail = {"picked": picked, "params": params, "splits": splits, "ref": ref,
              "ref_gmax": ref_gmax, "gaps": g, "trial_gap": trial_gap, "curve_at": curve_at}
    if sensitive:
        # The trials on which a fit is steady from run to run and still on
        # the steep part of its learning curve (PERF.md): the mean gap over
        # their splits and the searches.
        numbers["score_gap_sensitive"] = float(trial_gap[: int(sensitive["lowest"])].mean())
    if ref_gmax is not None:
        numbers["curve_gap_median"] = float(np.median(np.concatenate(
            [c.ravel() for c in curve_gaps]))) if curve_gaps else np.inf
    if ref_gmax is not None and "yardstick" in config and curve_at is not None:
        # How far rounding moves the curve differs sixfold from one dataset
        # to the next, so the gap is read in units of the gap that the plain
        # reference itself shows on the same data when it is computed as the
        # configuration's ``yardstick`` says (the stated precision).
        yard = reference(X, y, n_classes, params, splits, **config["yardstick"])
        numbers["yardstick_gap_median"] = float(np.median(
            curve_gap(yard["gmax"][:, :, curve_at].astype(np.float64), ref_gmax[:, :, curve_at])))
        numbers["curve_gap_vs_yardstick"] = numbers["curve_gap_median"] / max(
            numbers["yardstick_gap_median"], 1e-30)
    return numbers, detail


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, List[float]]]:
    """Every number that has a limit has to lie at or under it. A number
    that is missing or not finite fails, and stands in the table as 1e30 so
    that the result line stays JSON."""
    table = {k: [float(numbers[k]) if np.isfinite(numbers.get(k, np.inf)) else 1e30, float(lim)]
             for k, lim in limits.items()}
    return all(v <= lim for v, lim in table.values()), table
