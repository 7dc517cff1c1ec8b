"""Host time of the streamed engine outside its block dispatches and its
waits, per search of the window: each streamed chunk's
``executor.dispatch`` wall less what its ``stream.pass`` spans spent
blocked on a block (``wait_s``) and enqueueing the block programs
(``dispatch_s``), less its ``executor.wait`` children (the per-step sync on
the device) and its programs' ``executor.compile`` lookup. What is left is
the solver's loop on the host: each pass's set-up, the step's update and
extrapolation enqueued, the turn after each sync, during which the device
has nothing queued. No streamed chunk with ``stream.pass`` spans returns
nothing."""
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


LAYER, UNIT, SOURCE, MOVES = "trial engine", "ms/search", "program_span", "trials_per_s"


def host_seconds(chunk) -> float:
    wall = _streamed().wall
    inner = sum(float(p["attrs"].get("wait_s", 0.0)) + float(p["attrs"].get("dispatch_s", 0.0))
                for p in chunk["passes"])
    return wall(chunk["dispatch"]) - inner - sum(wall(w) for w in chunk["waits"])


def read(ctx):
    return _streamed().per_search(ctx["searches"], lambda chunks: 1e3 * sum(
        host_seconds(c) for c in chunks))
