"""Bytes the streamed engine uploaded in a warm search, per search of the
window: the summed ``uploaded_bytes`` of the streamed chunks'
``stream.pass`` spans (the same bytes ``tpuml_stream_bytes_total`` adds up).
Reads 0 while the stage cache holds the whole table; a block or cache
policy that lets blocks go between passes shows here first. No streamed
chunk with ``stream.pass`` spans returns nothing."""
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


LAYER, UNIT, SOURCE, MOVES = "stage cache", "GB/search", "program_span", "trials_per_s"


def read(ctx):
    return _streamed().per_search(ctx["searches"], lambda chunks: 1e-9 * sum(
        float(p["attrs"].get("uploaded_bytes", 0)) for c in chunks for p in c["passes"]))
