"""Time the host spent blocked on a row block that was not ready, per
search of the window: the summed ``wait_s`` of the streamed chunks'
``stream.pass`` spans (the same seconds ``tpuml_stream_wait_seconds_total``
adds up, a pass at a time). Reads about 0 while the stage cache holds every
block and the prefetch thread keeps the next one ready. No streamed chunk
with ``stream.pass`` spans returns nothing."""
import importlib.util
import os
import sys


def _streamed():
    """``lib/streamed.py``, loaded by its path as ``run.load_module`` does."""
    name = "perfbench_lib_streamed"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "lib", "streamed.py")
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


LAYER, UNIT, SOURCE, MOVES = "stage cache", "ms/search", "program_span", "trials_per_s"


def read(ctx):
    return _streamed().per_search(ctx["searches"], lambda chunks: 1e3 * sum(
        float(p["attrs"].get("wait_s", 0.0)) for c in chunks for p in c["passes"]))
