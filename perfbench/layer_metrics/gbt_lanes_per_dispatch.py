"""(Trial, split) lanes one program of a boosted search carries: the mean of
``n_trials x split_lanes`` over the traced search's chunked
``executor.dispatch`` spans: what the engine's memory
throttle lets it batch (48 when eight trials' six folds ride together).
No such span returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "count", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(ctx["traced_search"]["job_id"])
    lanes = [float(s["attrs"]["n_trials"]) * float(s["attrs"]["split_lanes"])
             for s in (TRACER.spans_for(tid) if tid else [])
             if s["name"] == "executor.dispatch" and s["attrs"].get("engine") == "chunked"
             and "split_lanes" in s["attrs"]]
    return sum(lanes) / len(lanes) if lanes else None
