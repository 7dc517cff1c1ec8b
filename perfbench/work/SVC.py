"""Work of one search of the SVC family (RBF, exact dual), from shapes alone.

Each split's fit needs the RBF Gram of its training rows (n_train^2 x d
multiply-adds), and each step of its dual ascent streams that Gram once in
bfloat16 (n_train^2 x 2 bytes: every machine's rows lie in it, so one pass
serves all of them); scoring is the held-out rows against the training rows
(n_test x n_train x d multiply-adds). How many steps a split's ascent runs
depends on the data and on ``C`` (the KKT stop), so the shapes give the
bytes of a step and the program's counter or spans give the steps
(``layer_metrics/svc_fit_roofline.py``). Rows a split masks are not work:
the program's [n, n] Gram over the whole table is its own choice.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def split_rows(n: int, n_folds: int, test_size: float) -> List[Tuple[float, float]]:
    """(training rows, held-out rows) of each split: the holdout first
    (sklearn rounds the test share up), then each fold on the average."""
    n_test = int(-(-n * test_size // 1))
    return [(float(n - n_test), float(n_test))] + [(n - n / n_folds, n / n_folds)] * n_folds


def gram_flops(n_train: float, d: int) -> float:
    return 2.0 * n_train * n_train * d


def score_flops(n_test: float, n_train: float, d: int) -> float:
    return 2.0 * n_test * n_train * d


def step_bytes(n_train: float) -> float:
    """One ascent step of one split: its training rows' bfloat16 Gram."""
    return 2.0 * n_train * n_train


def search_work(cell: Dict[str, Any], flops) -> Dict[str, float]:
    ds = cell["config"]["dataset"]
    n, d = int(ds["n_samples"]), int(ds["n_features"])
    T, K = int(cell["traffic"]["n_iter"]), int(cell["traffic"]["cv"])
    rows = split_rows(n, K, float(cell["traffic"]["test_size"]))
    # the ascent's FLOPs depend on the steps it runs; the shapes alone give
    # the Gram builds and the scoring (search_mfu reads these two)
    return {"fit_flops": T * sum(gram_flops(tr, d) for tr, _ in rows),
            "score_flops": T * sum(score_flops(te, tr, d) for tr, te in rows),
            "lane_step_bytes": sum(step_bytes(tr) for tr, _ in rows) / len(rows)}
