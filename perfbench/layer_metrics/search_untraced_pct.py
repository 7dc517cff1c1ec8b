"""Share of a search that no span of the program names, mean over the
window: of the ``client.train`` span's interval, the part that the union of
the trace's other spans leaves uncovered. The containers (``client.wait``,
``job.execute``, ``executor.batch``) are left out of the union: they say
that something ran, not what. The reader's own arithmetic. A search whose
``client.train`` does not span the whole call (no ``client.wait`` child) is
left out; none left returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "coordinator", "%", "program_span", "trials_per_s"
CONTAINERS = ("client.train", "client.wait", "job.execute", "executor.batch")


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    shares = []
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        spans = TRACER.spans_for(tid) if tid else []
        train = [s for s in spans if s["name"] == "client.train"]
        if not train or not any(s["name"] == "client.wait" for s in spans):
            continue  # no span of the whole call (before PR 26 it closed at submit)
        t0, t1 = min(s["start"] for s in train), max(s["end"] for s in train)
        if t1 <= t0:
            continue
        covered, upto = 0.0, t0
        for a, b in sorted((max(s["start"], t0), min(s["end"], t1))
                           for s in spans if s["name"] not in CONTAINERS):
            if b > max(a, upto):
                covered, upto = covered + b - max(a, upto), b
        shares.append(1.0 - covered / (t1 - t0))
    return 100.0 * sum(shares) / len(shares) if shares else None
