"""Host time of a search outside the trial engine: submit, expansion, the
split plan, result ingest and aggregation. Mean search wall of the window
less the engine's own phase walls (stage, compile, dispatch, fetch)."""
LAYER, UNIT, SOURCE, MOVES = "coordinator", "ms/search", "host_clock", "trials_per_s"


def read(ctx):
    a, b = ctx["counters"]["window_start"], ctx["counters"]["window_end"]
    n = len(ctx["searches"])
    engine = sum(b[f"phase_{p}_s"] - a[f"phase_{p}_s"] for p in ("stage", "compile", "dispatch", "fetch"))
    return 1e3 * (sum(s["wall_s"] for s in ctx["searches"]) - engine) / n
