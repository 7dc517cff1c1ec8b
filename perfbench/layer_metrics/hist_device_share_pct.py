"""The level histograms' share of the device's busy time in the traced
search: what of a tree fit is histogram, and what is frontier selection,
routing and the rest. The ops are found as ``hist_level_roofline`` finds
them. No device trace or no histogram op returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "trials_per_s"
KERNEL = r"level_histogram"  # where the work file gives no pattern of its own


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None or tr["busy_s_fullest"] <= 0:
        return None
    t = ctx["trace_reduce"].matching_seconds(
        tr["events"][tr["fullest"]], (ctx.get("work") or {}).get("hist_op_pattern", KERNEL), tr["labels"])
    return 100.0 * t / tr["busy_s_fullest"] if t > 0 else None
