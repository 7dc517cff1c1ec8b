"""How unevenly the chips of a mesh were busy in the traced search: (the
fullest chip's busy time - the emptiest's) over the fullest's, from the
union of each device's op intervals. One traced device, or none, returns
nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "%", "device_trace", "trials_per_s"


def read(ctx):
    tr = ctx["trace"]
    busy = list((tr or {}).get("busy_s_by_device", {}).values())
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
