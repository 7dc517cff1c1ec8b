"""What the streamed engine's spans say about one search: its chunks
(``executor.dispatch`` spans with ``engine=streamed`` and the block plan
the program chose, ``block_rows`` / ``n_blocks``), and each chunk's passes
over the row blocks (``stream.pass`` spans: ``kind``, ``blocks``,
``uploaded_bytes``, ``cache_hits``, ``wait_s``, ``dispatch_s``), its waits
for the device (``executor.wait``) and its programs' ``executor.compile``.
A program without those spans (before PR 40) gives no chunk, and the
readers that use this file then return nothing."""

from __future__ import annotations

from typing import Any, Dict, List


def job_spans(search: Dict[str, Any]) -> List[Dict[str, Any]]:
    from cs230_distributed_machine_learning_tpu.obs import TRACER

    tid = TRACER.trace_for_job(search["job_id"])
    return TRACER.spans_for(tid) if tid else []


def chunks(search: Dict[str, Any]) -> List[Dict[str, Any]]:
    """One entry a streamed chunk of the search: its dispatch span and the
    spans directly under it."""
    spans = job_spans(search)
    out = []
    for d in spans:
        if (d["name"] == "executor.dispatch" and d["attrs"].get("engine") == "streamed"
                and "block_rows" in d["attrs"]):
            kids = [s for s in spans if s["parent_id"] == d["span_id"]]
            out.append({"dispatch": d, "passes": [s for s in kids if s["name"] == "stream.pass"],
                        "waits": [s for s in kids if s["name"] in ("executor.wait", "executor.compile")]})
    return out


def per_search(searches, value) -> float | None:
    """The mean over the searches of ``value(chunks)``, over the searches
    that have a streamed chunk; None where none has."""
    got = [value(c) for c in (chunks(s) for s in searches) if c]
    return sum(got) / len(got) if got else None


def wall(span: Dict[str, Any]) -> float:
    return span["end"] - span["start"]
