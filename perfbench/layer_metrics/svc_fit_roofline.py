"""The exact-dual SVC fit's share of its roofline: the least time the chip
could take for the traced search's useful work, over the device's busy
seconds in that search.

The work is ``work/SVC.py``'s: each split's RBF Gram of its training rows
and the scoring at the compute peak, and every ascent step streaming the
split's training-row Gram in bfloat16 once at the HBM peak, for the steps
the search's ``executor.dispatch`` spans say its lanes ran (their
``svm.steps_sum`` where ``svm.solver`` is ``exact``). The busy seconds are
all of the search's device time (Gram builds, power iterations, ascents,
intercepts, scoring): nothing else runs in this cell's search. A program
without those span attributes, or a trace without a device, gives nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "trials_per_s"


def ascent_steps(search):
    """The steps the search's exact-dual lanes ran, summed; None where no
    dispatch span says them."""
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(search["job_id"])
    said = [s["attrs"]["svm.steps_sum"] for s in (TRACER.spans_for(tid) if tid else [])
            if s["name"] == "executor.dispatch" and s["attrs"].get("svm.solver") == "exact"]
    return sum(said) if said else None


def least_seconds(work, steps, peaks, flops):
    stream, _ = flops.roofline(0.0, steps * work["lane_step_bytes"], peaks)
    build, _ = flops.roofline(work["fit_flops"] + work["score_flops"], 0.0, peaks)
    return stream + build


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None or ctx["peaks"] is None or tr["busy_s_fullest"] <= 0:
        return None
    steps = ascent_steps(ctx["traced_search"])
    if not steps:
        return None
    return 100.0 * least_seconds(ctx["work"], steps, ctx["peaks"], ctx["flops"]) / tr["busy_s_fullest"]
