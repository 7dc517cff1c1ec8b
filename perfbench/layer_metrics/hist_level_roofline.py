"""The level histograms' share of their roofline: the least time the chip
could take for the fit's bin-and-scatter work (training rows x features
considered x stats, a level: ``work/RandomForestClassifier.py``), over the
summed device time of the ops that compute the histograms in the traced
search, found by what the trace calls them (``hist_op_pattern`` of the work
file: the Pallas kernel's name, the XLA form's row loop). The same work
whatever implements it. No such op in the trace returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "trials_per_s"
KERNEL = r"level_histogram"  # where the work file gives no pattern of its own


def read(ctx):
    return ctx["trace_reduce"].kernel_roofline_pct(
        ctx, (ctx.get("work") or {}).get("hist_op_pattern", KERNEL))
