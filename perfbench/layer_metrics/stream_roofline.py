"""The streamed LogReg block solver's share of its roofline: the least time
the chip could take for the work its programs (``power_block``,
``grad_block``, ``eval_block``) do in the traced search, over the summed
device time of those programs' ops.

The work is the streamed form's, counted here from the shapes the
program's spans give (the chunk's ``block_rows``, ``n_blocks``,
``n_trials``, ``split_lanes`` and its ``stream.pass`` spans by ``kind``)
and the configuration's widths: every padded row of every block for every
lane, whatever its fold weight; a ``step`` pass two matmuls of [rows, d+1]
by [d+1, lanes x classes] and the lanes' [T, S, rows, c] logits written and
read once in float32; a ``power`` pass two matmuls of [rows, d+1] by [d+1,
S]; the ``eval`` pass one logits matmul (its argmax can take the logits
where they are made); each pass reading its blocks (float32), fold
weights and labels. Each kind is priced
at its own bound (the power passes are memory-bound, the steps
compute-bound) and the least times add. The ops are found by the block
height in the shapes of the event's HLO text (``block_pattern``): every op
of the three programs has a [.., rows, ..] operand or result, and nothing
else the search runs does. No streamed chunk in the traced search, or no
such op in the trace, returns nothing."""
import importlib.util
import os
import sys

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "trials_per_s"


def _streamed():
    """``lib/streamed.py``, loaded by its path as ``run.load_module`` does."""
    name = "perfbench_lib_streamed"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "lib", "streamed.py")
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def block_pattern(rows: int) -> str:
    """A shape in HLO text with the block height as one of its extents."""
    return rf"[\[,]{int(rows)}[\],]"


def pass_work(kind: str, n_pad: int, d: int, c: int, splits: int, lanes: int):
    """(FLOPs, bytes) of one pass of ``kind`` over ``n_pad`` block rows."""
    dp = d + 1
    read = n_pad * (4.0 * d + 4.0 * splits + 4.0)  # blocks, fold weights, labels
    if kind == "power":
        return 2 * 2.0 * n_pad * dp * splits, n_pad * (4.0 * d + 4.0 * splits)
    if kind == "step":  # the logits written and read once, float32
        return 2 * 2.0 * n_pad * dp * lanes * c, read + 8.0 * lanes * n_pad * c
    return 2.0 * n_pad * dp * lanes * c, read  # eval: an argmax can take the logits in place


def least_seconds(chunks, d: int, c: int, peaks, flops) -> float:
    total = 0.0
    for ch in chunks:
        a = ch["dispatch"]["attrs"]
        n_pad, splits = int(a["block_rows"]) * int(a["n_blocks"]), int(a["split_lanes"])
        lanes = int(a["n_trials"]) * splits
        for p in ch["passes"]:
            f, b = pass_work(p["attrs"]["kind"], n_pad, d, c, splits, lanes)
            total += flops.roofline(f, b, peaks)[0]
    return total


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None or ctx["peaks"] is None:
        return None
    chunks = _streamed().chunks(ctx["traced_search"])
    if not chunks:
        return None
    t = sum(ctx["trace_reduce"].matching_seconds(
        tr["events"][tr["fullest"]], block_pattern(rows), tr["labels"])
        for rows in {int(ch["dispatch"]["attrs"]["block_rows"]) for ch in chunks})
    if t <= 0:
        return None
    ds = ctx["cell"]["config"]["dataset"]
    least = least_seconds(chunks, int(ds["n_features"]), max(int(ds["n_classes"]), 2),
                          ctx["peaks"], ctx["flops"])
    return 100.0 * least / t
