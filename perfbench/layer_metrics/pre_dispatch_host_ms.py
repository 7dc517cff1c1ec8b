"""Host time before the chip gets work, per search of the window: from the
start of the program's ``client.train`` span to the start of its first
``executor.dispatch`` span. A search without both returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "coordinator", "ms/search", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    gaps = []
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        spans = TRACER.spans_for(tid) if tid else []
        train = [s["start"] for s in spans if s["name"] == "client.train"]
        dispatch = [s["start"] for s in spans if s["name"] == "executor.dispatch"]
        if train and dispatch:
            gaps.append(min(dispatch) - min(train))
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
