"""Device time the fullest chip spent in cross-chip collectives in the
traced search: the ops whose HLO name says all-reduce, all-gather,
reduce-scatter, collective-permute or all-to-all, outermost matches summed.
A search on one traced device returns nothing; a mesh search whose trace
holds no such op reads 0 (XLA placed none)."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "ms/search", "device_trace", "trials_per_s"
COLLECTIVES = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None or len(tr.get("busy_s_by_device", {})) < 2:
        return None
    return 1e3 * ctx["trace_reduce"].matching_seconds(tr["events"][tr["fullest"]], COLLECTIVES)
