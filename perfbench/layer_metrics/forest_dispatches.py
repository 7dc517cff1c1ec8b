"""Programs the chunked engine enqueued in the traced search: the sum of
its ``executor.dispatch`` spans' ``dispatches`` (init + steps + evals of
each bucket). No span that says so returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "count/search", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(ctx["traced_search"]["job_id"])
    counts = [s["attrs"]["dispatches"] for s in (TRACER.spans_for(tid) if tid else [])
              if s["name"] == "executor.dispatch" and s["attrs"].get("engine") == "chunked"
              and "dispatches" in s["attrs"]]
    return float(sum(counts)) if counts else None
