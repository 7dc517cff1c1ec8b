"""Device time of one boosting stage on one split: the device's busy time
in the traced search over its trials x splits x stages. The stage count is
the program's own where its chunked ``executor.dispatch`` spans say
``stages`` (n_trials x splits x stages of every batch), and the
configuration's where they do not (a program from before the attribute):
``tree_split_device_ms`` with the program's own divisor. No device trace
returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "ms", "device_trace", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tr = ctx.get("trace")
    if not tr or tr["fullest"] is None or tr["busy_s_fullest"] <= 0:
        return None
    cfg, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    splits = int(traffic["cv"]) + 1
    tid = TRACER.trace_for_job(ctx["traced_search"]["job_id"])
    said = [s["attrs"] for s in (TRACER.spans_for(tid) if tid else [])
            if s["name"] == "executor.dispatch" and "stages" in s["attrs"]]
    stage_fits = sum(int(a["n_trials"]) * splits * int(a["stages"]) for a in said) or (
        int(traffic["n_iter"]) * splits * int(cfg["estimator"]["params"].get("n_estimators", 100)))
    return 1e3 * tr["busy_s_fullest"] / stage_fits
