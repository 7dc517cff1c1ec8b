"""The trial engine's fetch phase (host-clock wall of the blocking result
fetches) per search of the window."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "ms/search", "program_counter", "trials_per_s"


def read(ctx):
    a, b = ctx["counters"]["window_start"], ctx["counters"]["window_end"]
    return 1e3 * (b["phase_fetch_s"] - a["phase_fetch_s"]) / len(ctx["searches"])
