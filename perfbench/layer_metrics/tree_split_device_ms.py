"""Device time of one tree on one split: the device's busy time in the
traced search over its trials x splits x trees. No device trace returns
nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "ms", "device_trace", "trials_per_s"


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None or tr["busy_s_fullest"] <= 0:
        return None
    cfg, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    fits = (int(traffic["n_iter"]) * (int(traffic["cv"]) + 1)
            * int(cfg["estimator"]["params"].get("n_estimators", 100)))
    return 1e3 * tr["busy_s_fullest"] / fits
