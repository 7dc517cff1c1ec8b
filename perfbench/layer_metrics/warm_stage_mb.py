"""Bytes staged on the device again by warm searches, per search of the
window: ``bytes`` summed over the program's ``executor.stage`` spans whose
``outcome`` is ``miss``. A count: the same searches stage the same bytes.
Should read 0 (every staged form of a constant dataset and fold plan is a
cache hit). No ``executor.stage`` span with an ``outcome`` (before PR 26 the
name was a phase laid out from a timer) returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "stage cache", "MB/search", "program_counter", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    total, found = 0.0, False
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        for s in TRACER.spans_for(tid) if tid else []:
            if s["name"] == "executor.stage" and "outcome" in s["attrs"]:
                found = True
                if s["attrs"]["outcome"] == "miss":
                    total += float(s["attrs"].get("bytes") or 0)
    return total / 1e6 / len(ctx["searches"]) if found else None
