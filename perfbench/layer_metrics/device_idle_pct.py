"""Share of the traced search in which no op ran on the busiest device."""
LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", "trials_per_s"


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None:
        return None
    return 100.0 * (1.0 - tr["busy_s_fullest"] / tr["window_s"])
