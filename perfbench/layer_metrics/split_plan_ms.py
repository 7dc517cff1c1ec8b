"""Host time inside ``build_split_plan`` per search of the window: the
summed length of the program's ``executor.split_plan`` spans (a leaf span,
so its length is its self time). No such span returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "coordinator", "ms/search", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    total, found = 0.0, False
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        for s in TRACER.spans_for(tid) if tid else []:
            if s["name"] == "executor.split_plan":
                total, found = total + (s["end"] - s["start"]), True
    return 1e3 * total / len(ctx["searches"]) if found else None
