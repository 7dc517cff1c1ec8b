"""The trial engine's dispatch phase (host-clock wall around the device
call, less the blocking fetches inside it) per search of the window."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "ms/search", "program_counter", "trials_per_s"


def read(ctx):
    a, b = ctx["counters"]["window_start"], ctx["counters"]["window_end"]
    return 1e3 * (b["phase_dispatch_s"] - a["phase_dispatch_s"]) / len(ctx["searches"])
