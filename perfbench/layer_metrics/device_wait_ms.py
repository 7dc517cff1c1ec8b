"""Time the host spent waiting for the device, per search of the window:
the summed wall of the program's ``executor.wait`` spans whose ``on`` is
``result``, ``argmax``, ``backpressure`` or ``first_run`` (a wait for the
compiler's threads, ``on=compile``, is not the device's), plus, for each
``executor.dispatch`` span, its wall less the waits nested in it less its
``cpu_s``: the thread blocked inside the runtime's own enqueue (a full
device queue). The same meaning on every engine, which the two phase timers
(``engine_dispatch_ms``, ``engine_fetch_ms``) do not have. No
``executor.wait`` span in any search, as in a program without them, returns
nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "ms/search", "program_span", "trials_per_s"
ON_DEVICE = ("result", "argmax", "backpressure", "first_run")


def job_spans(search):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(search["job_id"])
    return TRACER.spans_for(tid) if tid else []


def wait_seconds(spans):
    """Seconds of one search the host waited for the device, or None where
    the search has no ``executor.wait`` span."""
    waits = [s for s in spans if s["name"] == "executor.wait"]
    if not waits:
        return None
    total = sum(s["end"] - s["start"] for s in waits if s["attrs"].get("on") in ON_DEVICE)
    for d in spans:
        if d["name"] == "executor.dispatch" and d.get("cpu_s") is not None:
            nested = sum(w["end"] - w["start"] for w in waits if w["parent_id"] == d["span_id"])
            total += max(0.0, d["end"] - d["start"] - nested - d["cpu_s"])
    return total


def read(ctx):
    found = [w for w in (wait_seconds(job_spans(s)) for s in ctx["searches"]) if w is not None]
    return 1e3 * sum(found) / len(found) if found else None
