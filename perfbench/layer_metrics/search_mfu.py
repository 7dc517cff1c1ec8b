"""The whole search's share of the chips' peak: model FLOPs of the traced
search (fits on training rows, scores on held-out rows; no padding, no
masked rows, no idled steps) over its wall on the host's clock and the
chips' peak."""
LAYER, UNIT, SOURCE, MOVES = "whole step", "%", "host_clock", "trials_per_s"


def read(ctx):
    if ctx["peaks"] is None:
        return None
    work = ctx["work"]["fit_flops"] + ctx["work"]["score_flops"]
    return 100.0 * work / ctx["traced_search"]["wall_s"] / (ctx["chips"] * ctx["peaks"]["flops_per_s"])
