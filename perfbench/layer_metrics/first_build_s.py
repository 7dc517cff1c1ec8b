"""Seconds of the first search spent constructing executables: the summed
wall of its ``executor.compile`` spans (make the parts, the cost analysis'
trace, the pack spec, the export or the blob's load: the ``executor.build``
children say which) plus its ``executor.wait`` spans with ``on=compile``
(the chunked engine waiting for the compiler's worker threads). A fresh
executable's first run, which holds the backend's compile or load, is in
``first_device_wait_s`` (``on=first_run``). No ``executor.compile`` span
returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "platform", "s", "program_span", "first_search_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(ctx["first"]["job_id"])
    spans = TRACER.spans_for(tid) if tid else []
    if not any(s["name"] == "executor.compile" for s in spans):
        return None
    return sum(s["end"] - s["start"] for s in spans
               if s["name"] == "executor.compile"
               or (s["name"] == "executor.wait" and s["attrs"].get("on") == "compile"))
