"""Peak device memory of the fullest chip after the window: the runtime
allocator's own peak (the larger of its peak in use and its peak reserved)."""
LAYER, UNIT, SOURCE, MOVES = "device", "GB", "program_counter", "trials_per_s"


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9 if ctx["memory_peak_bytes"] else None
