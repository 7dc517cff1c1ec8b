"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are named ``/device:TPU:<i>``; their ``XLA Ops`` line holds
one event per executed op, control flow (``while``, ``conditional``, calls)
as parents that span their bodies. Busy time is the union of the op
intervals, so nesting cannot count twice; an op's own time is its duration
less its children's. Host planes hold ``TraceAnnotation`` spans on the same
clock, which name what the host was doing in each idle gap.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
LABEL_STATS = ("tf_op", "long_name", "hlo_category", "source")


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str) -> Dict:
    """{'devices': {idx: [(start_ns, end_ns, name), ...]}, 'labels': {op
    name: the framework-level label the profiler attached}, 'host': [(start,
    end, name), ...]} from one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Tuple[float, float, str]]] = {}
    labels: Dict[str, str] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = []
                for e in line.events:
                    events.append((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name))
                    if e.name not in labels:
                        labels[e.name] = " ".join(
                            str(v) for k, v in e.stats if k in LABEL_STATS)
                devices[int(m.group(1))] = sorted(events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name))
    return {"devices": devices, "labels": labels, "host": sorted(host)}


def union_seconds(events: Sequence[Tuple[float, float, str]]) -> float:
    busy, cur_s, cur_e = 0.0, None, None
    for s, e, _ in sorted(events):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy * 1e-9


def self_times(events: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds per op name, each event's duration less its children's."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0) * 1e-9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def matching_seconds(events, pattern: str, labels: Optional[Dict[str, str]] = None) -> float:
    """Summed duration of the outermost events whose name or label matches:
    a match nested in a match is its parent's time already."""
    rx = re.compile(pattern)
    labels = labels or {}
    total, open_until = 0.0, -1.0
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        if s >= open_until and rx.search(f"{name} {labels.get(name, '')}"):
            total += e - s
            open_until = e
    return total * 1e-9


def short_name(op: str) -> str:
    """An op's event name is its whole HLO text; keep the instruction name,
    and say where it is a Mosaic (Pallas) kernel."""
    head = op.split(" = ", 1)[0].strip()
    return head + (" [tpu_custom_call]" if "tpu_custom_call" in op else "")


def kernel_roofline_pct(ctx: Dict, pattern: str) -> Optional[float]:
    """A kernel's share of its roofline in the traced search: the least time
    the chips could take for the cell's kernel work (``ctx["work"]``) over
    the summed device time of the ops matching ``pattern``. Nothing to read
    (no device trace, no matching op, no peaks) returns nothing."""
    tr = ctx["trace"]
    if not tr or tr["fullest"] is None or ctx["peaks"] is None:
        return None
    t = matching_seconds(tr["events"][tr["fullest"]], pattern, tr["labels"])
    if t <= 0:
        return None
    least, _bound = ctx["flops"].roofline(ctx["work"]["kernel_flops"], ctx["work"]["kernel_bytes"],
                                          ctx["peaks"], ctx["chips"])
    return 100.0 * least / t


def idle_gaps(events, host, top: int = 5, span: str = "perfbench.search",
              min_ns: float = 0.0) -> List[Tuple[str, float]]:
    """The longest gaps in which no op ran, each named by the innermost host
    event that covers its middle. The host's ``span`` annotation bounds the
    window, so the stretch before the first op and after the last count
    (host and device clocks agree to about a millisecond)."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    if not merged:
        return []
    gaps = [(b[0] - a[1], a[1], b[0], "") for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= min_ns]
    bounds = [(hs, he) for hs, he, name in host if name == span]
    if bounds:
        hs, he = min(b[0] for b in bounds), max(b[1] for b in bounds)
        if merged[0][0] > hs:
            gaps.append((merged[0][0] - hs, hs, merged[0][0], " (before the first device op)"))
        if he > merged[-1][1]:
            gaps.append((he - merged[-1][1], merged[-1][1], he, " (after the last device op)"))
    out = []
    for dur, g0, g1, note in sorted(gaps, reverse=True)[:top]:
        mid = 0.5 * (g0 + g1)
        cover = [(he - hs, name) for hs, he, name in host if hs <= mid <= he]
        out.append(((min(cover)[1] if cover else "unannotated") + note, dur * 1e-9))
    return out


def reduce_trace(trace: Dict, window_s: float, n_chips: int) -> Dict:
    devs = {i: ev for i, ev in trace["devices"].items() if ev}
    used = sorted(devs)[:n_chips] if devs else []
    busy = {i: union_seconds(devs[i]) for i in used}
    fullest = max(busy, key=busy.get) if busy else None
    ops = self_times(devs[fullest]) if fullest is not None else {}
    return {
        "busy_s_by_device": busy,
        "busy_s": (sum(busy.values()) / len(busy)) if busy else 0.0,
        "busy_s_fullest": busy.get(fullest, 0.0),
        "window_s": window_s,
        "device_ops": [(short_name(k), v) for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle_gaps(devs[fullest], trace["host"], min_ns=1e4) if fullest is not None else [],
        "events": devs,
        "labels": trace.get("labels", {}),
        "fullest": fullest,
    }
