"""Share of the dispatched trial lanes that were padding, over the searches
of the window: ``lanes_padding`` summed over ``lanes`` summed, from the
program's ``executor.dispatch`` spans (a chunk is padded to the chunk size
the executable was compiled for, itself a multiple of the mesh's devices).
No ``executor.dispatch`` span with ``lanes`` returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "%", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    lanes = padding = 0.0
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        for s in TRACER.spans_for(tid) if tid else []:
            if s["name"] == "executor.dispatch" and "lanes" in s["attrs"]:
                lanes += float(s["attrs"]["lanes"])
                padding += float(s["attrs"].get("lanes_padding") or 0)
    return 100.0 * padding / lanes if lanes else None
