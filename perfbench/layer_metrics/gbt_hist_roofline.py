"""The boosted trees' level histograms' share of their roofline: the least
time the chip could take for the fit's bin-and-scatter work (training rows
x features x 2 statistics, a level a stage: ``work/
GradientBoostingClassifier.py``; memory-bound), over the summed device time
of the ops that compute the histograms in the traced search, found by what
the trace calls them (``hist_op_pattern`` of the work file: the XLA form's
row loops, known by their float32 accumulators). Useful work only, so the
share cannot pass 100%. The same number as ``hist_level_roofline`` and read
by its code: that entry's ``workloads`` cannot take this cell without an
edit to the manifest (PERF.md section 7). No such op in the trace returns
nothing."""
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


read = _accepted("hist_level_roofline").read
