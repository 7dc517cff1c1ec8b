"""Dispatches a search needed: the program's ``executor.dispatch`` spans
(one per chunk the trial engine enqueues; on a mesh a chunk is capped by
what the chips' memory holds) per search of the window. No such span
returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "count/search", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    count = 0
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        count += sum(1 for s in (TRACER.spans_for(tid) if tid else [])
                     if s["name"] == "executor.dispatch")
    return count / len(ctx["searches"]) if count else None
