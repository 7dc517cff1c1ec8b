"""Share of the process's first search that no span of the program names:
``search_untraced_pct``'s arithmetic (its reader's own code, loaded by its
path) over the first search instead of the window's. The first search holds
what the warm ones do not: the dataset's fingerprint and upload, the split
plan's one build, the executable's construction and first run. A first
search whose ``client.train`` does not span the whole call returns
nothing."""
import importlib.util
import os

LAYER, UNIT, SOURCE, MOVES = "coordinator", "%", "program_span", "first_search_s"


def _reader(name):
    """Another reader of this directory, loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_metrics_" + name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    return _reader("search_untraced_pct").read({"searches": [ctx["first"]]})
