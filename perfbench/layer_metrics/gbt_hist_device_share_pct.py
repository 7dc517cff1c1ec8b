"""The level histograms' share of the device's busy time in the traced
search of a boosted-trees cell: what of a stage is histogram, and what is
the split search, routing, leaf sums and the score update. The same number
as ``hist_device_share_pct`` and read by its code, through this cell's
``hist_op_pattern`` (``work/GradientBoostingClassifier.py``): that entry's
``workloads`` cannot take this cell without an edit to the manifest
(PERF.md section 7). No device trace or no histogram op returns nothing."""
import importlib.util
import os

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "trials_per_s"


def _accepted(name):
    """The accepted reader of the same number, loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_metrics_" + name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


read = _accepted("hist_device_share_pct").read
