"""Work of one search of the MLPClassifier family, from shapes alone."""

from __future__ import annotations

from typing import Any, Dict


def mlp_layer_macs(dims):
    all_layers = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return all_layers, all_layers - dims[0] * dims[1]


def mlp_fit(train_rows_per_epoch: float, dims, epochs: int, n_trials: int) -> float:
    """Minibatch epochs of an MLP: forward, weight gradients (every layer)
    and input gradients (every layer but the first), 2 FLOPs a multiply-add,
    over the training rows the epochs visit, for every (trial, split)."""
    all_l, upper = mlp_layer_macs(dims)
    return epochs * n_trials * train_rows_per_epoch * (2.0 * all_l + 2.0 * all_l + 2.0 * upper)


def mlp_epoch_bytes(dims, lanes: int, rows: float) -> float:
    """Least traffic of one epoch: each lane's parameters and two Adam
    moments read and written once (they can stay on chip within an epoch),
    and the epoch's rows in bf16 once."""
    n_params = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return lanes * n_params * 4.0 * 3 * 2 + rows * dims[0] * 2.0


def mlp_score(held_rows: float, dims, n_trials: int) -> float:
    return n_trials * 2.0 * held_rows * mlp_layer_macs(dims)[0]


def search_work(cell: Dict[str, Any], flops) -> Dict[str, float]:
    ds, est = cell["config"]["dataset"], cell["config"]["estimator"]
    n, d, c = int(ds["n_samples"]), int(ds["n_features"]), int(ds["n_classes"])
    T, K = int(cell["traffic"]["n_iter"]), int(cell["traffic"]["cv"])
    train, held = flops.split_rows(n, K, float(cell["traffic"]["test_size"]))
    dims = (d, *[int(h) for h in est["params"]["hidden_layer_sizes"]], c)
    epochs = int(est["params"]["max_iter"])
    bs = min(200, n) if est["params"].get("batch_size", "auto") == "auto" else int(est["params"]["batch_size"])
    visited = (n // bs) * bs / n  # the ragged tail of each epoch is dropped
    fit = mlp_fit(train * visited, dims, epochs, T)
    return {"fit_flops": fit, "score_flops": mlp_score(held, dims, T), "kernel_flops": fit,
            "kernel_bytes": epochs * mlp_epoch_bytes(dims, T * (K + 1), n * visited)}
