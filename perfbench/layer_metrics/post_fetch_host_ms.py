"""Host time after the chip's last result is on the host, per search of
the window: from the end of the program's last ``executor.fetch`` span to
the end of its ``client.train`` span (result assembly, emission, ingest,
aggregation, the client's wake-up and result read). A search without both,
or whose ``client.train`` ends before its last fetch (before PR 26 the span
closed when submit returned), is left out; none left returns nothing."""
LAYER, UNIT, SOURCE, MOVES = "coordinator", "ms/search", "program_span", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    gaps = []
    for search in ctx["searches"]:
        tid = TRACER.trace_for_job(search["job_id"])
        spans = TRACER.spans_for(tid) if tid else []
        train = [s["end"] for s in spans if s["name"] == "client.train"]
        fetch = [s["end"] for s in spans if s["name"] == "executor.fetch"]
        if train and fetch and max(train) >= max(fetch):  # a span of the whole call
            gaps.append(max(train) - max(fetch))
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
