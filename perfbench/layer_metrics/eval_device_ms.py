"""Device time of the scoring half of the traced search: the ops whose
name or label carries the program's ``tpuml.eval`` scope, outermost
matches summed, on the fullest device. No such op returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "ms/search", "device_trace", "trials_per_s"
SCOPE = r"tpuml\.eval\b"


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None:
        return None
    t = ctx["trace_reduce"].matching_seconds(tr["events"][tr["fullest"]], SCOPE, tr["labels"])
    return 1e3 * t if t > 0 else None
