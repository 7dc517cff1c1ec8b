"""What enqueueing a search's programs costs the host, per search of the
window: the summed ``cpu_s`` (thread CPU time) of the program's
``executor.dispatch`` spans. CPU time, so a thread that blocks on a full
device queue while it enqueues does not count here (that is
``device_wait_ms``); seconds here mean the runtime's enqueue computes, or
spins. No ``executor.dispatch`` span with ``cpu_s``, as in a program whose
spans do not carry it, returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "trial engine", "ms/search", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    totals = []
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        cpu = [s["cpu_s"] for s in (TRACER.spans_for(tid) if tid else [])
               if s["name"] == "executor.dispatch" and s.get("cpu_s") is not None]
        if cpu:
            totals.append(sum(cpu))
    return 1e3 * sum(totals) / len(totals) if totals else None
