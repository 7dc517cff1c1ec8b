"""Seconds of the first search spent in the trace and lowering the engine
makes only for XLA's cost analysis (``_capture_cost``: the job-cost
report's ``xla_flops`` / ``bytes_accessed``): the summed wall of its
``executor.build`` spans with ``stage=cost``. What the instrumentation
costs the measured run; 0 where no part of the search's executables is
priced (a mesh program, the chunked protocol). No ``executor.build`` span,
as in a program without them, returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "platform", "s", "program_span", "first_search_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(ctx["first"]["job_id"])
    builds = [s for s in (TRACER.spans_for(tid) if tid else []) if s["name"] == "executor.build"]
    if not builds:
        return None
    return sum(s["end"] - s["start"] for s in builds if s["attrs"].get("stage") == "cost")
