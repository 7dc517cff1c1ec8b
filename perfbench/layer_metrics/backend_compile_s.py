"""Seconds inside XLA's backend compile before the window (a persistent-
cache hit costs only the retrieval)."""
LAYER, UNIT, SOURCE, MOVES = "platform", "s", "program_counter", "first_search_s"


def read(ctx):
    return ctx["counters"]["window_start"]["xla_compile_s"]
