"""Executables' worth of buckets a forest search needed: the chunked
``executor.dispatch`` spans of the traced search (every trial whose static
hyperparameters differ is a bucket of its own, with its own init / step /
eval programs). No such span returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "count/search", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(ctx["traced_search"]["job_id"])
    count = sum(1 for s in (TRACER.spans_for(tid) if tid else [])
                if s["name"] == "executor.dispatch" and s["attrs"].get("engine") == "chunked")
    return float(count) if count else None
