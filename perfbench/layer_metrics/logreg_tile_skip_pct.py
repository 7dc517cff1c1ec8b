"""Share of the packed LogReg step kernel's (row tile, split) column groups
that the occupancy table let it skip, over the searches of the window: the
mean of ``tile_skip_pct`` over the program's ``executor.dispatch`` spans
that carry it (the share is the staged table's, read off it by the
kernel's ``dispatch_attrs``; 0.0 where the whole-slab body runs on every
tile). No such span, as on a
program without the table, returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    shares = []
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        for s in TRACER.spans_for(tid) if tid else []:
            if s["name"] == "executor.dispatch" and "tile_skip_pct" in s["attrs"]:
                shares.append(float(s["attrs"]["tile_skip_pct"]))
    return sum(shares) / len(shares) if shares else None
