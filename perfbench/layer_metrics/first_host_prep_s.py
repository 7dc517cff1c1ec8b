"""Seconds of the first search the host spent getting ready to build and
run: the union of its ``executor.load_data``, ``executor.split_plan``,
``executor.plan``, ``executor.prepare`` and ``executor.stage`` spans (a
union, so a mesh stage span and the upload nested in it count once). The
dataset's read and fingerprint, the one build of the fold plan, the
bucket's plan (in a process's first search: the import of a fused kernel's
Pallas module), the host's binning, the uploads and staged forms. An
``executor.stage`` without an ``outcome`` (before PR 26 the name was a
phase laid out from a timer) is not counted; none of those spans returns
nothing."""
LAYER, UNIT, SOURCE, MOVES = "coordinator", "s", "program_span", "first_search_s"
PREP = ("executor.load_data", "executor.split_plan", "executor.plan", "executor.prepare",
        "executor.stage")


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(ctx["first"]["job_id"])
    covered, upto, found = 0.0, float("-inf"), False
    for a, b in sorted((s["start"], s["end"]) for s in (TRACER.spans_for(tid) if tid else [])
                       if s["name"] in PREP
                       and (s["name"] != "executor.stage" or "outcome" in s["attrs"])):
        found = True
        if b > max(a, upto):
            covered, upto = covered + b - max(a, upto), b
    return covered if found else None
