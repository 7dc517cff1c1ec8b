"""Operations and bytes a search needs, from shapes alone: what every
family shares. A family's own work model is ``work/<estimator class>.py``
(``search_work(cell, flops)``: the model FLOPs of one search with the
kernel's share and the kernel's least traffic).

These take the shapes of the work (rows, features, classes, trials, splits,
steps), never the implementation, so a rewritten kernel reads against the
same work. Only training rows count for a fit and only held-out rows for a
score: rows with zero fold weight, padding lanes, padded columns and the
steps a converged trial idles through are the implementation's choice, not
work the search needs. (The program's own ``utils/flops.py`` counts every
row for every split and a third matmul the linear model does not have.)
"""

from __future__ import annotations

from typing import Dict


def split_rows(n: int, n_folds: int, test_size: float):
    """(training rows, held-out rows) summed over the K+1 splits: split 0 is
    the holdout, splits 1..K the folds."""
    n_test = int(-(-n * test_size // 1))  # sklearn rounds the test share up
    train = (n - n_test) + n_folds * (n - n / n_folds) if n_folds >= 2 else n - n_test
    held = n_test + (n if n_folds >= 2 else 0)
    return float(train), float(held)


def roofline(flops: float, nbytes: float, peaks: Dict[str, float], chips: int = 1):
    """(least seconds, which bound) on ``chips`` chips."""
    t_c = flops / (chips * peaks["flops_per_s"])
    t_m = nbytes / (chips * peaks["hbm_bytes_per_s"])
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
