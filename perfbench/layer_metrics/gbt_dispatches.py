"""Programs the chunked engine enqueued in the traced search of a boosted
bucket: the sum of ``dispatches`` (init + steps + evals of every fold
group, the curve's sampled evals among them) over its chunked
``executor.dispatch`` spans. The same number as ``forest_dispatches`` and
read by its code: that entry's ``workloads`` cannot take this cell without
an edit to the manifest (PERF.md section 7). No such span returns nothing."""
import importlib.util
import os

LAYER, UNIT, SOURCE, MOVES = "trial engine", "count/search", "program_span", "trials_per_s"


def _accepted(name):
    """The accepted reader of the same number, loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_metrics_" + name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


read = _accepted("forest_dispatches").read
