"""Seconds of the first search the host waited for the device:
``device_wait_ms``'s sum (its reader's own code, loaded by its path) over
the first search. It holds a fresh executable's first run (``on=first_run``:
the backend's compile or the load of its cached program, uploads still in
flight, and the fit) beside the waits every search has. A first search
without ``executor.wait`` spans returns nothing."""
import importlib.util
import os

LAYER, UNIT, SOURCE, MOVES = "trial engine", "s", "program_span", "first_search_s"


def _reader(name):
    """Another reader of this directory, loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_layer_metrics_" + name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    waits = _reader("device_wait_ms")
    return waits.wait_seconds(waits.job_spans(ctx["first"]))
