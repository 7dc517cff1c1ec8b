"""Executables built or compiled inside the window: executable-cache misses
plus XLA backend compiles. Should read 0."""
LAYER, UNIT, SOURCE, MOVES = "platform", "count", "program_counter", "trials_per_s"


def read(ctx):
    a, b = ctx["counters"]["window_start"], ctx["counters"]["window_end"]
    return (b["exe_cache_misses"] - a["exe_cache_misses"]) + (b["xla_compiles"] - a["xla_compiles"])
