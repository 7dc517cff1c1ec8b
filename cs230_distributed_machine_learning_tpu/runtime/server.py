"""Coordinator REST server: wire-compatible with the reference master.

Route parity with ``aws-prod/master/master.py:27-390`` (same paths, methods,
and response shapes — the home route enumerates them like master.py:30-44),
plus the reference scheduler's introspection endpoints (/workers, /queues —
scheduler.py:95-97,154-159) served from the placement engine when the
coordinator runs a cluster. SSE progress streaming (/train_status) keeps the
reference's event schema {job_status, tasks_pending, total_subtasks} with a
final event carrying job_result (master.py:237-266).

Built as a plain WSGI app on werkzeug (no Flask dependency): same
deployment surface, serve with ``serve()`` or any WSGI server.
"""

from __future__ import annotations

import json
from typing import Optional

from ..obs import (
    PARENT_HEADER,
    PROFILER,
    RECORDER,
    TIMESERIES,
    TRACE_HEADER,
    TRACER,
    activate,
    compare_critical_paths,
    counter_inc,
    export_trace,
    gauge_set,
    obs_enabled,
    observe,
    refresh_route_p99,
    render_prometheus,
    span,
    timeseries_sample,
)
from ..utils.serialization import json_safe
from .coordinator import Coordinator


#: Self-contained observability page (no external assets — fleets run
#: without egress). Tables over the JSON endpoints, 2 s auto-refresh.
_DASHBOARD_HTML = """<!doctype html>
<html><head><meta charset="utf-8"><title>tpuml coordinator</title>
<style>
 body{font:14px/1.45 system-ui,sans-serif;margin:24px;color:#1a1a1a;background:#fafafa}
 h1{font-size:18px;margin:0 0 4px} h2{font-size:15px;margin:24px 0 6px}
 table{border-collapse:collapse;width:100%;background:#fff}
 th,td{border:1px solid #ddd;padding:4px 8px;text-align:left;font-size:13px}
 th{background:#f0f0f0} .ok{color:#1a7f37} .bad{color:#b42318}
 #meta{color:#666;font-size:12px} code{background:#eee;padding:0 3px}
</style></head><body>
<h1>tpuml coordinator</h1>
<div id="meta">health: <span id="health">…</span> · refreshed <span id="ts">never</span>
 · JSON: <code>/jobs</code> <code>/workers</code> <code>/queues</code> <code>/supervisor</code>
 <code>/metrics/prom</code> <code>/metrics/history?name=</code> <code>/trace/&lt;job_id&gt;</code>
 <code>/critical_path/&lt;job_id&gt;</code> <code>/trace/&lt;job_id&gt;/export</code>
 <code>/cost/&lt;job_id&gt;</code> <code>/explain/&lt;job_id&gt;/&lt;subtask_id&gt;</code>
 <code>/curves/&lt;job_id&gt;</code> <code>/events</code> <code>/predictor/calibration</code> <code>/healthz</code>
 <code>/alerts</code> <code>/autoscale</code></div>
<h2>Jobs</h2><table id="jobs"><thead><tr><th>job</th><th>model</th><th>dataset</th>
<th>status</th><th>done</th><th>failed</th><th>pruned</th><th>diverged</th><th>total</th><th>session</th></tr></thead><tbody></tbody></table>
<h2>Learning curves (latest job)</h2>
<div id="curves" style="background:#fff;border:1px solid #ddd;padding:8px;font-size:12px">no curves yet</div>
<h2>Latest job trace</h2>
<div id="trace" style="background:#fff;border:1px solid #ddd;padding:8px;font-size:12px">no trace yet</div>
<h2>Critical path</h2>
<div id="critpath" style="background:#fff;border:1px solid #ddd;padding:8px;font-size:12px">no critical path yet</div>
<h2>Latest job cost</h2>
<div id="cost" style="background:#fff;border:1px solid #ddd;padding:8px;font-size:12px">no cost data yet</div>
<h2>Metrics history</h2>
<div id="spark" style="background:#fff;border:1px solid #ddd;padding:8px;font-size:12px">no samples yet</div>
<h2>Perf observatory</h2>
<div id="perfspark" style="background:#fff;border:1px solid #ddd;padding:8px;font-size:12px">no samples yet</div>
<h2>Fleet health</h2>
<div id="autoscale" style="background:#fff;border:1px solid #ddd;padding:8px;font-size:12px">no signals yet</div>
<table id="alerts"><thead></thead><tbody></tbody></table>
<h2>Flight recorder (latest events)</h2>
<table id="events"><thead></thead><tbody></tbody></table>
<h2>Workers</h2><table id="workers"><thead></thead><tbody></tbody></table>
<h2>Queues</h2><table id="queues"><thead></thead><tbody></tbody></table>
<h2>Supervised agents</h2><table id="sup"><thead></thead><tbody></tbody></table>
<script>
const get = u => fetch(u).then(r => r.ok ? r.json() : null).catch(() => null);
// quotes escaped too: esc() output lands inside attribute values (the
// trace rows' title tooltips), and attrs carry client-controlled strings
const esc = s => String(s ?? "").replace(/[&<>"']/g,
  c => ({"&":"&amp;","<":"&lt;",">":"&gt;",'"':"&quot;","'":"&#39;"}[c]));
// cell renderer: arrays (e.g. a worker's queued-subtask list) collapse to
// a count + sample, never one column per index
const cell = v => Array.isArray(v)
  ? `${v.length} queued${v.length ? ": " + v.slice(0, 3).join(", ") + (v.length > 3 ? ", …" : "") : ""}`
  : (typeof v === "object" && v ? JSON.stringify(v) : v);
function kvTable(el, obj){
  const rows = Object.entries(obj || {});
  if (!rows.length){ el.tBodies[0].innerHTML = "<tr><td>none</td></tr>"; el.tHead.innerHTML=""; return; }
  const plain = rows.every(([,v]) => typeof v !== "object" || !v || Array.isArray(v));
  const cols = plain ? null
    : [...new Set(rows.flatMap(([,v]) => Object.keys(v)))];
  el.tHead.innerHTML = plain
    ? "<tr><th>id</th><th>value</th></tr>"
    : "<tr><th>id</th>" + cols.map(c => `<th>${esc(c)}</th>`).join("") + "</tr>";
  el.tBodies[0].innerHTML = rows.map(([k, v]) =>
    `<tr><td>${esc(k)}</td>` + (plain
      ? `<td>${esc(cell(v))}</td>`
      : cols.map(c => `<td>${esc(cell(v[c]))}</td>`).join("")) + "</tr>").join("");
}
function listTable(el, arr){
  if (!arr || !arr.length){ el.tBodies[0].innerHTML = "<tr><td>none</td></tr>"; el.tHead.innerHTML=""; return; }
  const cols = Object.keys(arr[0]);
  el.tHead.innerHTML = "<tr>" + cols.map(c => `<th>${esc(c)}</th>`).join("") + "</tr>";
  el.tBodies[0].innerHTML = arr.map(r =>
    "<tr>" + cols.map(c => `<td>${esc(JSON.stringify(r[c]))}</td>`).join("") + "</tr>").join("");
}
// span-tree timeline: one row per span, bar offset/width proportional to
// [start, end] within the trace window, indented by tree depth
function renderTrace(el, data){
  if (!data || !data.spans || !data.spans.length){ el.textContent = "no trace yet"; return; }
  const t0 = Math.min(...data.spans.map(s => s.start));
  const t1 = Math.max(...data.spans.map(s => s.end));
  const total = Math.max(t1 - t0, 1e-6);
  const rows = [];
  const walk = (nodes, depth) => (nodes || []).forEach(n => {
    rows.push({n, depth}); walk(n.children, depth + 1); });
  walk(data.tree, 0);
  el.innerHTML =
    `<div style="color:#666">trace <code>${esc(data.trace_id)}</code> · ` +
    `${data.spans.length} spans · ${(total * 1000).toFixed(1)} ms</div>` +
    rows.map(({n, depth}) => {
      const off = 100 * (n.start - t0) / total;
      const w = Math.max(100 * (n.end - n.start) / total, 0.4);
      return `<div style="display:flex;align-items:center;margin:1px 0">` +
        `<span style="width:230px;padding-left:${depth * 12}px;overflow:hidden;` +
        `white-space:nowrap" title="${esc(JSON.stringify(n.attrs))}">${esc(n.name)}</span>` +
        `<span style="flex:1;position:relative;height:10px;background:#f4f4f4">` +
        `<span style="position:absolute;left:${off}%;width:${w}%;height:10px;` +
        `background:#4a7fb5"></span></span>` +
        `<span style="width:80px;text-align:right">${((n.end - n.start) * 1000).toFixed(1)} ms</span></div>`;
    }).join("");
}
// critical-path waterfall (GET /critical_path/<job_id>): one stacked bar
// tiling the job wall plus a ranked per-segment table; untraced slices
// render hatched-gray so coverage gaps are visible, not hidden
const SEG_COLORS = {
  "frontend.proxy": "#8e7cc3", "submit.http": "#6fa8dc", submit: "#4a7fb5",
  expand: "#3d6d9e", "queue.wait": "#e6b84c", place: "#c27ba0",
  "reclaim.wait": "#b42318", "executor.compile": "#93c47d",
  "executor.stage": "#76a5af", "executor.dispatch": "#45818e",
  "executor.fetch": "#6aa84f", execute: "#38761d",
  "result.ingest": "#a2c4c9", aggregate: "#674ea7", untraced: "#d9d9d9",
};
function renderCritPath(el, cp){
  if (!cp || !cp.segments || !cp.segments.length){
    el.textContent = "no critical path yet"; return; }
  const wall = Math.max(cp.wall_s, 1e-9);
  el.innerHTML =
    `<div style="color:#666">job <code>${esc(cp.job_id)}</code> · ` +
    `wall ${(cp.wall_s * 1000).toFixed(1)} ms · coverage ` +
    `${(100 * cp.coverage).toFixed(1)}% · dominant ` +
    `<b>${esc((cp.dominant || [])[0] || "")}</b>` +
    (cp.n_reclaims ? ` · <span class="bad">${esc(cp.n_reclaims)} reclaim(s)</span>` : "") +
    (cp.speculated ? ` · speculative win` : "") + `</div>` +
    `<div style="display:flex;height:18px;margin:6px 0;border:1px solid #ccc">` +
    cp.segments.map(s =>
      `<span title="${esc(s.name)} ${(s.duration_s * 1000).toFixed(1)} ms" ` +
      `style="width:${(100 * s.duration_s / wall).toFixed(3)}%;` +
      `background:${SEG_COLORS[s.name] || "#999"}"></span>`).join("") +
    `</div>` +
    `<table><thead><tr><th>segment</th><th>total</th><th>share</th></tr></thead><tbody>` +
    (cp.dominant || []).map(n =>
      `<tr><td><span style="display:inline-block;width:10px;height:10px;` +
      `background:${SEG_COLORS[n] || "#999"}"></span> ${esc(n)}</td>` +
      `<td>${((cp.totals[n] || 0) * 1000).toFixed(1)} ms</td>` +
      `<td>${(100 * (cp.totals[n] || 0) / wall).toFixed(1)}%</td></tr>`).join("") +
    `</tbody></table>`;
}
// SI-ish magnitude formatter for FLOP/byte counts
const fmt = n => n == null ? "\\u2013"
  : n >= 1e12 ? (n / 1e12).toFixed(2) + " T"
  : n >= 1e9 ? (n / 1e9).toFixed(2) + " G"
  : n >= 1e6 ? (n / 1e6).toFixed(2) + " M"
  : String(Math.round(n));
const pct = v => v == null ? "\\u2013" : (100 * v).toFixed(1) + "%";
// per-job device cost report (GET /cost/<job_id>): totals line + one row
// per executed (dataset, model) group
function renderCost(el, c){
  if (!c || !c.n_groups){ el.textContent = "no cost data yet"; return; }
  el.innerHTML =
    `<div style="color:#666">job <code>${esc(c.job_id)}</code> · ` +
    `${(c.device_seconds || 0).toFixed(3)} device-s · ` +
    `model FLOPs ${fmt(c.model_flops)} · bytes ${fmt(c.bytes_accessed)} · ` +
    `MFU ${c.mfu == null ? "n/a" : pct(c.mfu)}</div>` +
    `<table><thead><tr><th>model</th><th>dataset</th><th>trials</th>` +
    `<th>device-s</th><th>FLOPs</th><th>bytes</th><th>MFU</th>` +
    `<th>HBM peak</th></tr></thead><tbody>` +
    c.groups.map(g => `<tr><td>${esc(g.model_type)}</td>` +
      `<td>${esc(g.dataset_id)}</td><td>${esc(g.n_subtasks)}</td>` +
      `<td>${(g.device_seconds || 0).toFixed(3)}</td>` +
      `<td>${fmt(g.model_flops != null ? g.model_flops : g.xla_flops)}</td>` +
      `<td>${fmt(g.bytes_accessed)}</td><td>${pct(g.mfu)}</td>` +
      `<td>${fmt(g.hbm_peak_bytes)}</td></tr>`).join("") +
    `</tbody></table>`;
}
// sparkline panels over GET /metrics/history (the embedded time-series
// ring, obs/timeseries.py): per-worker queue depth and breaker state,
// the retry RATE derived from the counter's samples, and MFU per model
const SPARKS = [
  {name: "tpuml_worker_queue_depth", title: "queue depth", mode: "raw"},
  {name: "tpuml_subtasks_retried_total", title: "retries/s", mode: "rate"},
  {name: "tpuml_worker_breaker_state", title: "breaker state", mode: "raw"},
  {name: "tpuml_executor_mfu", title: "MFU", mode: "raw"},
];
// perf-observatory panel (docs/OBSERVABILITY.md "Perf observatory"):
// per-route p99 (the derived gauge the scrape refreshes) and the
// device-seconds-per-phase RATE (fraction of wall the device pipeline
// spends staging / compiling / dispatching / fetching)
const PERF_SPARKS = [
  {name: "tpuml_http_route_p99_seconds", title: "route p99 (s)", mode: "raw"},
  {name: "tpuml_executor_device_seconds_total",
   title: "device-s/s by phase", mode: "rate"},
  {name: "tpuml_sse_lag_seconds", title: "SSE lag (s)", mode: "raw"},
];
function sparkSvg(pts){
  if (pts.length < 2) return "";
  const t0 = pts[0][0], t1 = pts[pts.length - 1][0];
  const vs = pts.map(p => p[1]);
  const vmin = Math.min(...vs, 0), vmax = Math.max(...vs);
  const W = 160, H = 26;
  const poly = pts.map(([t, v]) =>
    `${(W * (t - t0) / Math.max(t1 - t0, 1e-9)).toFixed(1)},` +
    `${(H - 2 - (H - 4) * (v - vmin) / Math.max(vmax - vmin, 1e-9)).toFixed(1)}`
  ).join(" ");
  return `<svg width="${W}" height="${H}" style="background:#f4f4f4;vertical-align:middle">` +
    `<polyline points="${poly}" fill="none" stroke="#4a7fb5" stroke-width="1.5"/></svg>`;
}
// counter samples -> per-interval rate (clamped at 0: restarts reset)
const rate = s => s.slice(1).map((p, i) =>
  [p[0], Math.max(p[1] - s[i][1], 0) / Math.max(p[0] - s[i][0], 1e-9)]);
async function renderSparks(el, sparks){
  const blocks = await Promise.all(sparks.map(async p => {
    const h = await get(`/metrics/history?name=${p.name}`);
    const series = ((h && h.series) || []).filter(s => s.samples.length > 1);
    if (!series.length) return "";
    return `<div style="margin:2px 0"><b>${esc(p.title)}</b> ` +
      series.slice(0, 8).map(s => {
        const pts = p.mode === "rate" ? rate(s.samples) : s.samples;
        if (!pts.length) return "";
        const last = pts[pts.length - 1][1];
        const lbl = Object.values(s.labels).join(",") || "total";
        return `<span style="margin-right:12px;white-space:nowrap">` +
          `${esc(lbl)} ${sparkSvg(pts)} <code>${(+last).toPrecision(3)}</code></span>`;
      }).join("") + `</div>`;
  }));
  const html = blocks.filter(Boolean).join("");
  el.innerHTML = html || "no samples yet";
}
// learning-curve panel (GET /curves/<job_id> — docs/OBSERVABILITY.md
// "Trial telemetry plane"): one sparkline per trial curve, drawn from
// the record's primary channel (loss > score > gmax), split 0. Diverged
// trials are flagged; None points (non-finite on device) are skipped.
function renderCurves(el, c){
  if (!c || !c.curves || !c.curves.length){ el.textContent = "no curves yet"; return; }
  el.innerHTML =
    `<div style="color:#666">job <code>${esc(c.job_id)}</code> · ` +
    `${c.n_curves} curves · ${c.tasks_diverged || 0} diverged</div>` +
    c.curves.slice(-10).map(e => {
      const rec = e.curve || {};
      const ch = rec.loss ? "loss" : (rec.score ? "score" : "gmax");
      const row = ((rec[ch] || [])[0] || []);
      const pts = row.map((v, i) => [i, v]).filter(p => p[1] != null && isFinite(p[1]));
      const tail = (rec.tail || [])[0];
      return `<div style="margin:2px 0;white-space:nowrap">` +
        `<code>${esc(e.subtask_id)}</code> r${esc(e.rung)} ` +
        sparkSvg(pts) + ` <b>${esc(ch)}</b>` +
        (tail == null ? "" : ` tail <code>${(+tail).toPrecision(3)}</code>`) +
        (e.diverged ? ` <span class="bad">diverged</span>` : "") + `</div>`;
    }).join("");
}
// fleet health panel (docs/OBSERVABILITY.md "Fleet health plane"):
// the derived capacity signals + per-rule alert states
function renderHealth(scaleEl, alertsEl, sc, al){
  if (sc && sc.desired_workers != null){
    const held = sc.hysteresis && sc.hysteresis.scale_down_held;
    const sig = sc.signals || {};
    scaleEl.innerHTML =
      `desired workers <b>${esc(sc.desired_workers)}</b> (live ${esc(sc.live_workers)})` +
      ` \\u00b7 desired shards <b>${esc(sc.desired_shards)}</b> (now ${esc(sc.n_shards)})` +
      (held ? ` \\u00b7 <span class="bad">scale-down held (drain)</span>` : "") +
      `<div style="color:#666">backlog ${esc(sig.backlog_seconds)} s \\u00b7 ` +
      `inflight ${esc(sig.inflight_jobs)} jobs / ${esc(sig.pending_subtasks)} subtasks \\u00b7 ` +
      `admission ${esc(((sig.admission_utilization || 0) * 100).toFixed(0))}% \\u00b7 ` +
      `p99 ${esc(sig.route_p99_s)} s \\u00b7 pressure ${esc(sig.pressure)}</div>`;
  } else scaleEl.textContent = "no signals yet";
  const rows = ((al && al.alerts) || []).map(a => ({
    rule: a.rule,
    state: a.state === "firing" ? "\\u25cf firing" : a.state,
    value: a.value == null ? "\\u2013" : (+a.value).toPrecision(3),
    threshold: `${a.cmp} ${a.threshold}`, severity: a.severity,
    since: a.for_s == null ? "" : `${a.for_s.toFixed(0)}s`,
  }));
  listTable(alertsEl, rows);
}
// flight-recorder feed: the newest events, newest first
async function renderEvents(el, ev){
  const rows = ((ev && ev.events) || []).slice(-15).reverse().map(e => ({
    seq: e.seq, kind: e.kind,
    subtask: e.subtask_id ? `${(e.job_id || "").slice(0, 8)}/${e.subtask_id}` : "",
    worker: e.worker_id || "", attempt: e.attempt == null ? "" : e.attempt,
    detail: JSON.stringify(e.data).slice(0, 120),
  }));
  listTable(el, rows);
}
async function tick(){
  // fire-and-forget scrape: refreshes the derived gauges (route p99) and
  // drives the time-series sampler even on direct-mode coordinators that
  // have no sweep loop and no external Prometheus
  fetch("/metrics/prom").catch(() => {});
  const [h, jobs, workers, queues, sup, ev, al, sc] = await Promise.all(
    ["/health", "/jobs", "/workers", "/queues", "/supervisor",
     "/events?limit=500", "/alerts", "/autoscale"].map(get));
  const he = document.getElementById("health");
  he.textContent = h ? h.status : "unreachable";
  he.className = h && h.status === "ok" ? "ok" : "bad";
  document.getElementById("jobs").tBodies[0].innerHTML =
    (Array.isArray(jobs) ? jobs : []).map(j => `<tr>
    <td>${esc(j.job_id)}</td><td>${esc(j.model_type)}</td><td>${esc(j.dataset_id)}</td>
    <td class="${j.status === "completed" ? "ok" : (j.status === "failed" || j.status === "completed_with_failures") ? "bad" : ""}">${esc(j.status)}</td>
    <td>${esc(j.completed_subtasks)}</td><td>${esc(j.failed_subtasks)}</td>
    <td>${esc(j.pruned_subtasks || 0)}</td>
    <td class="${j.diverged_subtasks ? "bad" : ""}">${esc(j.diverged_subtasks || 0)}</td>
    <td>${esc(j.total_subtasks)}</td><td>${esc((j.session_id || "").slice(0, 8))}</td></tr>`).join("")
    || "<tr><td colspan=10>no jobs yet</td></tr>";
  kvTable(document.getElementById("workers"), workers);
  kvTable(document.getElementById("queues"), queues);
  listTable(document.getElementById("sup"), sup);
  renderEvents(document.getElementById("events"), ev);
  renderHealth(document.getElementById("autoscale"),
               document.getElementById("alerts"), sc, al);
  await renderSparks(document.getElementById("spark"), SPARKS);
  await renderSparks(document.getElementById("perfspark"), PERF_SPARKS);
  const latest = Array.isArray(jobs) && jobs.length ? jobs[0].job_id : null;
  renderTrace(document.getElementById("trace"),
              latest ? await get(`/trace/${latest}`) : null);
  renderCritPath(document.getElementById("critpath"),
                 latest ? await get(`/critical_path/${latest}`) : null);
  renderCurves(document.getElementById("curves"),
               latest ? await get(`/curves/${latest}`) : null);
  renderCost(document.getElementById("cost"),
             latest ? await get(`/cost/${latest}`) : null);
  document.getElementById("ts").textContent = new Date().toLocaleTimeString();
}
tick(); setInterval(tick, 2000);
</script></body></html>
"""


def create_app(coordinator: Optional[Coordinator] = None):
    from werkzeug.exceptions import HTTPException, NotFound
    from werkzeug.routing import Map, Rule
    from werkzeug.wrappers import Request, Response

    coord = coordinator or Coordinator()

    url_map = Map(
        [
            Rule("/", endpoint="home", methods=["GET"]),
            Rule("/health", endpoint="health", methods=["GET"]),
            Rule("/create_session", endpoint="create_session", methods=["POST"]),
            Rule("/download_data/<sid>", endpoint="download_data", methods=["POST"]),
            Rule("/check_data/<sid>", endpoint="check_data", methods=["GET"]),
            Rule("/preprocess/<sid>", endpoint="preprocess", methods=["POST"]),
            Rule("/train/<sid>", endpoint="train", methods=["POST"]),
            Rule("/train_status/<sid>", endpoint="train_status", methods=["POST"]),
            Rule("/check_status/<sid>/<jid>", endpoint="check_status", methods=["GET"]),
            Rule("/metrics/<sid>/<jid>", endpoint="metrics", methods=["GET"]),
            Rule("/download_model/<sid>/<jid>", endpoint="download_model", methods=["GET"]),
            Rule("/workers", endpoint="workers", methods=["GET"]),
            Rule("/queues", endpoint="queues", methods=["GET"]),
            Rule("/supervisor", endpoint="supervisor", methods=["GET"]),
            # visual observability (the reference ran kafka-ui for this,
            # docker-compose.yml:69-84): one self-contained HTML page over
            # the JSON introspection endpoints + a flat job feed
            Rule("/jobs", endpoint="jobs", methods=["GET"]),
            Rule("/dashboard", endpoint="dashboard", methods=["GET"]),
            # observability plane (docs/OBSERVABILITY.md): Prometheus
            # exposition of the unified metrics registry, per-job span
            # trees, the agents' span-shipping ingest, the per-job device
            # cost report, and the deep-health probe
            Rule("/metrics/prom", endpoint="metrics_prom", methods=["GET"]),
            # on-demand deep profiling (docs/OBSERVABILITY.md "Perf
            # observatory"): bracket a live workload with a programmatic
            # jax.profiler capture dumped under <journal_dir>/profile/
            Rule("/profile/start", endpoint="profile_start", methods=["POST"]),
            Rule("/profile/stop", endpoint="profile_stop", methods=["POST"]),
            Rule("/profile/status", endpoint="profile_status", methods=["GET"]),
            Rule("/trace/<jid>", endpoint="trace", methods=["GET"]),
            Rule("/trace/<jid>/export", endpoint="trace_export",
                 methods=["GET"]),
            Rule("/critical_path/<jid>", endpoint="critical_path_report",
                 methods=["GET"]),
            Rule("/trace_spans/<wid>", endpoint="trace_spans", methods=["POST"]),
            Rule("/cost/<jid>", endpoint="cost", methods=["GET"]),
            Rule("/healthz", endpoint="healthz", methods=["GET"]),
            # liveness/readiness split (docs/ROBUSTNESS.md "Coordinator
            # recovery"): /livez answers as long as the process serves;
            # /readyz is 503 until journal replay + in-flight re-queue
            # finished, so load balancers and the chaos harness can gate
            Rule("/livez", endpoint="livez", methods=["GET"]),
            Rule("/readyz", endpoint="readyz", methods=["GET"]),
            # flight recorder + explainability (docs/OBSERVABILITY.md
            # "Flight recorder"): per-subtask decision timelines, the
            # event firehose, predictor calibration, and the embedded
            # metrics time-series history
            Rule("/explain/<jid>/<stid>", endpoint="explain", methods=["GET"]),
            Rule("/explain/<jid>", endpoint="explain_job", methods=["GET"]),
            # trial telemetry plane (docs/OBSERVABILITY.md "Trial
            # telemetry plane"): per-trial learning curves captured
            # in-fit, plus the numerical-health watchdog's verdicts
            Rule("/curves/<jid>", endpoint="curves_job", methods=["GET"]),
            Rule("/curves/<jid>/<stid>", endpoint="curves_subtask",
                 methods=["GET"]),
            Rule("/events", endpoint="events", methods=["GET"]),
            # fleet health plane (docs/OBSERVABILITY.md "Fleet health
            # plane"): SLO alert states and the derived capacity signals
            # an external autoscaler acts on
            Rule("/alerts", endpoint="alerts", methods=["GET"]),
            Rule("/autoscale", endpoint="autoscale", methods=["GET"]),
            Rule("/metrics/history", endpoint="metrics_history",
                 methods=["GET"]),
            Rule("/predictor/calibration", endpoint="predictor_calibration",
                 methods=["GET"]),
            # worker-agent control plane (reference scheduler.py:95-159)
            Rule("/subscribe", endpoint="subscribe", methods=["POST"]),
            Rule("/unsubscribe/<wid>", endpoint="unsubscribe", methods=["POST"]),
            Rule("/heartbeat/<wid>", endpoint="heartbeat", methods=["POST"]),
            Rule("/next_tasks/<wid>", endpoint="next_tasks", methods=["GET"]),
            Rule("/task_result/<wid>", endpoint="task_result", methods=["POST"]),
            Rule("/task_metrics/<wid>", endpoint="task_metrics", methods=["POST"]),
            # dataset distribution for remote agents: the DCN replacement
            # for the reference's shared EFS volume (compose.yml:92-94)
            Rule("/dataset/<dataset_id>", endpoint="dataset", methods=["GET"]),
            # SPMD slice liveness: every rank of a multi-process mesh
            # heartbeats here, and each rank's watchdog reads the others'
            # ages — a SIGKILLed sibling is detected even while survivors
            # block inside a collective (runtime/agent._slice_watchdog)
            Rule("/slice_heartbeat/<slice_id>/<int:rank>",
                 endpoint="slice_heartbeat", methods=["POST"]),
            Rule("/slice_status/<slice_id>", endpoint="slice_status",
                 methods=["GET"]),
            # shard-to-shard rebalancing plane (docs/ROBUSTNESS.md "Shard
            # rebalancing"): peers dialing peers, never client traffic
            Rule("/migrate_in", endpoint="migrate_in", methods=["POST"]),
            Rule("/steal_candidates", endpoint="steal_candidates",
                 methods=["GET"]),
            Rule("/steal_tasks", endpoint="steal_tasks", methods=["POST"]),
            Rule("/peer_result", endpoint="peer_result", methods=["POST"]),
        ]
    )

    import threading as _threading
    import time as _time

    _slices: dict = {}
    _slices_lock = _threading.Lock()

    def _json(data, status=200):
        return Response(
            json.dumps(json_safe(data)), status=status, mimetype="application/json"
        )

    def home(request):
        return _json(
            {
                "service": "tpuml-coordinator",
                "endpoints": [
                    "POST /create_session",
                    "POST /download_data/<session_id>",
                    "GET  /check_data/<session_id>?dataset_name=",
                    "POST /preprocess/<session_id>",
                    "POST /train/<session_id>",
                    "POST /train_status/<session_id>  (SSE)",
                    "GET  /check_status/<session_id>/<job_id>",
                    "GET  /metrics/<session_id>/<job_id>",
                    "GET  /download_model/<session_id>/<job_id>",
                    "GET  /workers",
                    "GET  /queues",
                    "GET  /jobs",
                    "GET  /dashboard  (HTML)",
                    "GET  /metrics/prom  (Prometheus exposition)",
                    "POST /profile/start  (on-demand jax.profiler capture)",
                    "POST /profile/stop",
                    "GET  /profile/status",
                    "GET  /metrics/history?name=&since=  (embedded time series)",
                    "GET  /trace/<job_id>  (span tree)",
                    "GET  /trace/<job_id>/export?format=perfetto|otlp",
                    "GET  /critical_path/<job_id>[?compare=<job_id>]",
                    "GET  /cost/<job_id>  (device cost report)",
                    "GET  /explain/<job_id>/<subtask_id>  (decision timeline)",
                    "GET  /curves/<job_id>[/<subtask_id>]  (learning curves)",
                    "GET  /events?since=&limit=  (flight-recorder firehose)",
                    "GET  /predictor/calibration  (predicted-vs-actual stats)",
                    "GET  /health",
                    "GET  /healthz  (deep health: device, workers, stragglers)",
                    "GET  /livez  (liveness probe)",
                    "GET  /readyz  (readiness: 503 while recovering)",
                ],
            }
        )

    def health(request):
        out = {"status": "ok"}
        if coord.shard_id is not None:
            out["shard"] = coord.shard_id
            out["n_shards"] = coord.n_shards
        sup = getattr(coord, "agent_supervisor", None)
        if sup is not None:
            slots = sup.status()
            out["agent_slots"] = {
                "alive": sum(1 for s in slots if s["alive"]),
                "total": len(slots),
                "gave_up": sum(1 for s in slots if s["gave_up"]),
            }
            if out["agent_slots"]["gave_up"] == len(slots) and slots:
                out["status"] = "degraded"  # every executor slot is down
        return _json(out)

    def _priority_or_400(value, default=0):
        """Malformed client input must 400, not 500 out of int()."""
        if value is None:
            return default
        try:
            return int(value)
        except (TypeError, ValueError):
            from werkzeug.exceptions import BadRequest

            raise BadRequest(f"priority must be an integer, got {value!r}")

    def create_session(request):
        # optional body {"session_id": ..., "priority": ...}: a sharded
        # front end mints the session id itself (so shard_of(sid) and the
        # owning shard agree — runtime/sharding.py) and may carry the
        # session's QoS lane; a bare POST keeps the legacy mint-here path
        body = request.get_json(force=True, silent=True) or {}
        sid_req = body.get("session_id")
        if sid_req is not None:
            from werkzeug.exceptions import BadRequest

            if coord.shard_id is None:
                # unsharded coordinators always mint server-side (the
                # legacy contract): honoring client ids here would let
                # two clients silently share — and read — one session
                # via the idempotent re-create path
                sid_req = None
            else:
                from .sharding import shard_of

                if shard_of(sid_req, coord.n_shards) != coord.shard_id:
                    # a session stored here but hashing elsewhere would
                    # be permanently unreachable through the front ends
                    raise BadRequest(
                        f"session id {sid_req!r} hashes to shard "
                        f"{shard_of(sid_req, coord.n_shards)}, not this "
                        f"shard ({coord.shard_id})"
                    )
        sid = coord.create_session(
            sid_req, priority=_priority_or_400(body.get("priority")),
        )
        out = {"session_id": sid}
        if coord.shard_id is not None:
            out["shard"] = coord.shard_id
        return _json(out, status=201)

    def download_data(request, sid):
        body = request.get_json(force=True)
        return _json(
            coord.download_data(
                sid, body["dataset_url"], body["dataset_name"], body["dataset_type"]
            )
        )

    def check_data(request, sid):
        return _json(coord.check_data(sid, request.args["dataset_name"]))

    def preprocess(request, sid):
        body = request.get_json(force=True)
        return _json(coord.preprocess(sid, body["dataset_id"], body.get("config")))

    def _admission_reject(sid):
        """429/503 + Retry-After for a submit the coordinator must not
        accept (admission caps, or recovery still in progress) — the
        overload contract of docs/ROBUSTNESS.md. None when admitted."""
        rejection = coord.admission_check(sid)
        if rejection is None:
            return None
        return Response(
            json.dumps(json_safe({
                "status": "rejected",
                "reason": rejection["reason"],
                "retry_after_s": rejection["retry_after_s"],
            })),
            status=rejection["status"],
            mimetype="application/json",
            headers={"Retry-After": f"{rejection['retry_after_s']:g}"},
        )

    def train(request, sid):
        reject = _admission_reject(sid)
        if reject is not None:
            return reject
        body = request.get_json(force=True)
        if "priority" in body:
            body["priority"] = _priority_or_400(body["priority"], None)
        return _json(coord.submit_train(sid, body))

    def train_status(request, sid):
        body = request.get_json(force=True)
        # an SSE RESUME (known job_id) is a read, not new load — it must
        # never be rejected, or a reconnecting client could not follow the
        # job it already owns through the very overload that dropped it.
        # The lookup uses the CANONICAL (shard-stamped) id: a client
        # resuming under its own minted id must still match.
        known = bool(
            body.get("job_id")
            and coord.store.has_job(
                sid, coord.canonical_job_id(body["job_id"])
            )
        )
        if known:
            # a resume against a job this shard ALREADY handed off must
            # redirect, not resubmit: re-running it here would mint a
            # second live copy of a job the recipient shard now owns
            moved = _moved(coord.canonical_job_id(body["job_id"]))
            if moved is not None:
                return moved
        if not known:
            reject = _admission_reject(sid)
            if reject is not None:
                return reject
        if "priority" in body:
            body["priority"] = _priority_or_400(body["priority"], None)
        submit = coord.submit_train(sid, body)
        job_id = submit["job_id"]

        def stream():
            # Time-to-first-event: the first progress snapshot is yielded
            # immediately (stream_status reads before its first tick
            # sleep), but common SSE clients buffer reads — http.client's
            # chunked read(amt) blocks until ~amt BYTES accumulate, which
            # used to delay the first ~150-byte event by 3+ ticks
            # (loadtest_single_shard.json: sse_first_event p50 4.9 s).
            # A 2 KB comment prologue (ignored by every SSE parser)
            # overflows those buffers so the immediate snapshot is
            # actually DELIVERED immediately.
            yield ":" + " " * 2048 + "\n\n"
            # SSE-lag SLO signal: the stream's producer yields one event
            # then sleeps one tick, so anything beyond the tick between
            # consecutive yields is delivery lag — store-read time, GIL
            # contention, and client/socket backpressure (the previous
            # yield blocks until the subscriber drained it)
            tick = coord.config.service.sse_tick_s
            prev = _time.monotonic()
            for progress in coord.stream_status(sid, job_id):
                now = _time.monotonic()
                gauge_set(
                    "tpuml_sse_lag_seconds", max(now - prev - tick, 0.0)
                )
                prev = now
                yield f"data: {json.dumps(json_safe(progress))}\n\n"

        return Response(stream(), mimetype="text/event-stream")

    def _moved(jid):
        """Forwarding stamp for a migrated job: 409 with the destination
        shard, or None when this shard still owns the job. Front ends
        (runtime/frontend.py) turn the 409 into a cached redirect."""
        dest = coord.store.migrated_to(jid)
        if dest is None:
            return None
        return _json(
            {"status": "moved", "migrated_to": dest, "job_id": jid},
            status=409,
        )

    def check_status(request, sid, jid):
        # canonicalize like the SSE-resume path: a client polling under
        # its own minted id must reach the shard-stamped job
        jid = coord.canonical_job_id(jid)
        moved = _moved(jid)
        if moved is not None:
            return moved
        return _json(coord.check_status(sid, jid))

    def metrics(request, sid, jid):
        # ?wait=1: block until the job finalizes before replying — opt-in
        # parity with the reference master's /metrics, which blocked until
        # every subtask had reported (master.py:325-332). The default stays
        # non-blocking (returns whatever has reported so far); see
        # docs/API.md "Differences from the reference".
        jid = coord.canonical_job_id(jid)
        moved = _moved(jid)
        if moved is not None:
            return moved
        if request.args.get("wait"):
            timeout = float(
                request.args.get("timeout", coord.config.service.client_timeout_s)
            )
            coord._require_session(sid)
            coord.store.wait_job(sid, jid, timeout)
        return _json(coord.job_metrics(sid, jid))

    def metrics_prom(request):
        # refresh point-in-time gauges at scrape time: fleet size, the
        # per-worker health families, and local-device HBM
        if coord.cluster is not None:
            gauge_set("tpuml_workers_alive", len(coord.cluster.engine.workers))
            coord.cluster.engine.refresh_health_metrics()
        from .executor import record_hbm_gauges

        record_hbm_gauges()
        # derived SLO gauges: per-route p99 from the request histogram —
        # refreshed here so the time-series ring samples a p99 without
        # sampling histogram buckets
        refresh_route_p99()
        # each scrape also feeds the embedded time-series ring (throttled;
        # the sweep is the other driver) — direct-mode coordinators have
        # no sweep loop, so history still accumulates at scrape cadence
        timeseries_sample()
        # ... and drives the fleet-health tick (capacity signals + alert
        # rules, throttled) for the same no-sweep reason, so the
        # autoscale/alert gauges in THIS exposition are current
        coord.health_tick()
        return Response(
            render_prometheus(),
            content_type="text/plain; version=0.0.4; charset=utf-8",
        )

    #: profiler error reasons -> HTTP status: disabled valve is 503 (come
    #: back when obs is on), an open/absent capture is 409 (conflict with
    #: the profiler's state), a backend/filesystem failure is a real 500
    _PROFILE_STATUS = {"disabled": 503, "busy": 409, "idle": 409,
                       "backend": 500}

    def profile_start(request):
        """Begin an on-demand jax.profiler capture (obs/devprof.py). Body
        (optional JSON): ``{"tag": "..."}`` names the dump directory under
        ``<journal_dir>/profile/``. 409 while a capture is already open,
        503 when observability is disabled, 500 when the backend profiler
        or the dump filesystem refuses."""
        body = request.get_json(force=True, silent=True) or {}
        out = PROFILER.start(body.get("tag"))
        if out["status"] == "started":
            return _json(out, status=201)
        return _json(out, status=_PROFILE_STATUS.get(out.get("reason"), 500))

    def profile_stop(request):
        """Finish the active capture; returns the dump directory and file
        count. 409 when no capture is open; 500 on a failed stop (the
        capture stays active for a retry unless the backend reports the
        session already gone)."""
        out = PROFILER.stop()
        if out["status"] == "stopped":
            return _json(out, status=200)
        return _json(out, status=_PROFILE_STATUS.get(out.get("reason"), 500))

    def profile_status(request):
        return _json(PROFILER.status())

    def cost(request, jid):
        """Per-job device cost report (docs/OBSERVABILITY.md): device-
        seconds, total FLOPs/bytes, HBM high-water, per-group MFU."""
        report = coord.job_cost(coord.canonical_job_id(jid))
        if report is None:
            return _json(
                {"status": "error", "message": f"no job {jid!r}"}, status=404
            )
        return _json(report)

    def healthz(request):
        """Deep health, beyond /health's liveness ping: local device
        reachability + memory, per-worker health (EWMA batch latency,
        heartbeat age, failure ratio, queue depth), and the flagged
        straggler list. Always HTTP 200; ``status`` says ok/degraded.
        ``ready``/``recovery`` mirror /readyz (journal replay state)."""
        out = {
            "status": "ok",
            "obs_enabled": obs_enabled(),
            "ready": coord.ready,
        }
        if coord.shard_id is not None:
            out["shard"] = coord.shard_id
            out["n_shards"] = coord.n_shards
        if coord.recovery:
            out["recovery"] = coord.recovery
        if not coord.ready:
            out["status"] = "degraded"
        try:
            import jax

            from ..utils.backend import device_memory_stats

            devices = jax.local_devices()
            dev = {
                "reachable": True,
                "platform": devices[0].platform,
                "n_devices": len(devices),
                "device_kind": str(getattr(devices[0], "device_kind", "")),
            }
            stats = device_memory_stats()
            mem = {
                k: stats[k]
                for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats
            }
            if mem:
                dev["memory"] = mem
        except Exception as e:  # noqa: BLE001 — unreachable backend IS the finding
            dev = {"reachable": False, "error": str(e)}
            out["status"] = "degraded"
        out["device"] = dev
        if coord.cluster is not None:
            snap = coord.cluster.engine.refresh_health_metrics()
            out["n_workers"] = len(snap)
            out["workers"] = snap
            # undelivered bus backlog per topic: a deep `train` backlog
            # means placements are outrunning the executor pool
            out["bus_depths"] = coord.cluster.bus.depths()
            out["queue_depths"] = {
                wid: h["queue_depth"] for wid, h in snap.items()
            }
            out["stragglers"] = sorted(
                wid for wid, h in snap.items() if h["straggler"]
            )
            if out["stragglers"] or not snap:
                out["status"] = "degraded"
        sup = getattr(coord, "agent_supervisor", None)
        if sup is not None:
            slots = sup.status()
            out["agent_slots"] = {
                "alive": sum(1 for s in slots if s["alive"]),
                "total": len(slots),
                "gave_up": sum(1 for s in slots if s["gave_up"]),
            }
            if slots and out["agent_slots"]["gave_up"] == len(slots):
                out["status"] = "degraded"
        return _json(out)

    def livez(request):
        """Pure liveness: the process answers requests. Never inspects
        recovery, workers, or devices — a recovering or degraded
        coordinator is still ALIVE (restarting it would only lose the
        recovery progress)."""
        return _json({"status": "ok"})

    def readyz(request):
        """Readiness: 200 only once journal replay + in-flight re-queue
        finished (``Coordinator.ready``). 503 + Retry-After while
        recovering, so load balancers hold traffic and the chaos harness
        can gate on recovery completion."""
        if coord.ready:
            return _json({"status": "ready", "recovery": coord.recovery})
        retry_after = coord.config.service.admission_retry_after_s
        return Response(
            json.dumps(json_safe({
                "status": "recovering", "recovery": coord.recovery,
            })),
            status=503,
            mimetype="application/json",
            headers={"Retry-After": f"{retry_after:g}"},
        )

    def explain(request, jid, stid):
        """Per-subtask decision timeline from the flight recorder: who
        placed it where and why (score breakdown), lease grant/reclaim,
        attempts/retries, speculation, terminal result — 404 when the
        recorder never saw the pair."""
        try:
            return _json(coord.explain(coord.canonical_job_id(jid), stid))
        except KeyError as e:
            return _json(
                {"status": "error", "message": str(e).strip("'")}, status=404
            )

    def explain_job(request, jid):
        """Subtask ids with a recorded timeline for the job — the
        discovery aid for /explain/<jid>/<stid>."""
        jid = coord.canonical_job_id(jid)
        stids = RECORDER.job_subtasks(jid)
        if not stids:
            return _json(
                {"status": "error",
                 "message": f"no recorded events for job {jid!r}"},
                status=404,
            )
        return _json({"job_id": jid, "subtask_ids": stids})

    def curves_job(request, jid):
        """All recorded learning curves for a job (docs/OBSERVABILITY.md
        "Trial telemetry plane"): one entry per (trial, rung, attempt)
        with the downsampled per-split trace and the watchdog's diverged
        flag. 404 for an unknown job; a known job with no curves yet
        returns an empty list."""
        jid = coord.canonical_job_id(jid)
        moved = _moved(jid)
        if moved is not None:
            return moved
        out = coord.job_curves(jid)
        if out is None:
            return _json(
                {"status": "error", "message": f"no job {jid!r}"}, status=404
            )
        return _json(out)

    def curves_subtask(request, jid, stid):
        """One trial's curve history across rungs/attempts — 404 when the
        pair never reported a curve (CS230_CURVES=0, or evicted)."""
        jid = coord.canonical_job_id(jid)
        moved = _moved(jid)
        if moved is not None:
            return moved
        try:
            return _json(coord.subtask_curves(jid, stid))
        except KeyError as e:
            return _json(
                {"status": "error", "message": str(e).strip("'")}, status=404
            )

    def events(request):
        """Flight-recorder firehose: events with seq > ?since= (oldest
        first, at most ?limit=). ``last_seq`` is the cursor for the next
        poll."""
        def _int_arg(name, default):
            try:
                return int(request.args.get(name, default))
            except ValueError:
                return default  # a malformed value falls back alone

        since = _int_arg("since", 0)
        limit = _int_arg("limit", 1000)
        evts, last = RECORDER.events(since=since, limit=limit)
        return _json({"events": evts, "n_events": len(evts), "last_seq": last})

    def alerts(request):
        """Fleet-health alert states (obs/slo.py): one entry per rule
        with its live ok/pending/firing state. Reading evaluates the
        rules first (throttled; ``?force=1`` bypasses the floor), so a
        poller never sees a state staler than the evaluation interval —
        direct-mode coordinators have no sweep to keep it fresh."""
        coord.health_tick(force=bool(request.args.get("force")))
        out = coord.alerts.snapshot()
        if coord.shard_id is not None:
            out["shard"] = coord.shard_id
        return _json(out)

    def autoscale(request):
        """Derived capacity signals (obs/signals.py): the
        desired_workers/desired_shards an external autoscaler acts on,
        with the raw signals and the hysteresis verdict that produced
        them. Evaluates first like /alerts."""
        coord.health_tick(force=bool(request.args.get("force")))
        out = dict(coord.signals.report())
        if coord.shard_id is not None:
            out["shard"] = coord.shard_id
        return _json(out)

    def metrics_history(request):
        """Embedded time-series read (obs/timeseries.py): ?name= selects a
        metric family, ?since= (epoch seconds) trims old samples. Without
        ?name=, lists the sampled family names."""
        name = request.args.get("name")
        if not name:
            return _json({"names": TIMESERIES.names()})
        try:
            since = float(request.args.get("since", 0.0))
        except ValueError:
            since = 0.0
        return _json({
            "name": name,
            "since": since,
            "series": TIMESERIES.history(name, since=since),
        })

    def predictor_calibration(request):
        """Per-model-family predicted-vs-actual calibration of the
        runtime predictor (docs/OBSERVABILITY.md "Predictor
        calibration")."""
        return _json(coord.predictor_calibration())

    def trace(request, jid):
        jid = coord.canonical_job_id(jid)
        tid = TRACER.trace_for_job(jid)
        if tid is None:
            return _json(
                {"status": "error", "message": f"no trace for job {jid!r}"},
                status=404,
            )
        spans = sorted(
            TRACER.spans_for(tid), key=lambda s: (s.get("start") or 0)
        )
        return _json(
            {
                "job_id": jid,
                "trace_id": tid,
                "n_spans": len(spans),
                "spans": spans,
                "tree": TRACER.tree(tid),
            }
        )

    def trace_export(request, jid):
        """Export a job's trace as an interchange document
        (obs/export.py): ``?format=perfetto`` (default — Chrome trace
        JSON for ui.perfetto.dev / chrome://tracing) or ``?format=otlp``
        (OTLP-shaped JSON). The document is written under the journal
        dir (``trace_<trace_id>.<format>.json``) and returned inline;
        400 on an unknown format, 404 when no trace is bound."""
        jid = coord.canonical_job_id(jid)
        tid = TRACER.trace_for_job(jid)
        if tid is None:
            return _json(
                {"status": "error", "message": f"no trace for job {jid!r}"},
                status=404,
            )
        fmt = request.args.get("format", "perfetto")
        try:
            out = export_trace(
                tid,
                sorted(TRACER.spans_for(tid),
                       key=lambda s: (s.get("start") or 0)),
                fmt,
                job_id=jid,
            )
        except ValueError as e:
            return _json({"status": "error", "message": str(e)}, status=400)
        return _json(out)

    def critical_path_report(request, jid):
        """Per-job latency attribution (docs/OBSERVABILITY.md "Critical
        path & trace export"): the span tree joined with flight-recorder
        events, tiled into segments that sum to the measured wall.
        ``?compare=<job_id>`` additionally diffs against that job as the
        baseline (``diff.delta_wall_s`` > 0 means this job is slower)."""
        report = coord.critical_path(coord.canonical_job_id(jid))
        if report is None:
            return _json(
                {"status": "error",
                 "message": f"no critical path for job {jid!r} "
                            "(no trace bound)"},
                status=404,
            )
        baseline_id = request.args.get("compare")
        if baseline_id:
            baseline = coord.critical_path(
                coord.canonical_job_id(baseline_id)
            )
            if baseline is None:
                return _json(
                    {"status": "error",
                     "message": f"no critical path for baseline job "
                                f"{baseline_id!r}"},
                    status=404,
                )
            report = dict(report)
            report["diff"] = compare_critical_paths(baseline, report)
        return _json(report)

    def trace_spans(request, wid):
        """Span-shipping ingest for remote agents (runtime/agent.py
        _ship_spans): the return leg of the X-Trace-Id propagation."""
        body = request.get_json(force=True, silent=True) or {}
        n = TRACER.ingest(body.get("spans") or [])
        counter_inc("tpuml_trace_spans_ingested_total", n)
        return _json({"status": "ok", "ingested": n})

    def download_model(request, sid, jid):
        moved = _moved(coord.canonical_job_id(jid))
        if moved is not None:
            return moved
        path = coord.best_model_path(sid, coord.canonical_job_id(jid))
        if path is None:
            return _json({"status": "error", "message": "no model artifact"}, status=404)
        with open(path, "rb") as f:
            payload = f.read()
        return Response(
            payload,
            mimetype="application/octet-stream",
            headers={"Content-Disposition": f"attachment; filename={jid}_best_model.pkl"},
        )

    def workers(request):
        if coord.cluster is None:
            return _json({})
        return _json(coord.cluster.engine.worker_snapshot())

    def queues(request):
        if coord.cluster is None:
            return _json({})
        return _json(coord.cluster.engine.queue_snapshot())

    def supervisor(request):
        sup = getattr(coord, "agent_supervisor", None)
        return _json(sup.status() if sup is not None else [])

    def jobs(request):
        return _json(coord.store.jobs_overview())

    def dashboard(request):
        return Response(_DASHBOARD_HTML, mimetype="text/html")

    def _cluster_or_400():
        if coord.cluster is None:
            from werkzeug.exceptions import BadRequest

            raise BadRequest("coordinator is not running a cluster")
        return coord.cluster

    def subscribe(request):
        from werkzeug.exceptions import BadRequest

        body = request.get_json(silent=True) or {}
        # n_devices / mesh_shape: the worker's mesh-slice report — the
        # placement engine's predictor-aware packing divisor
        # (docs/ARCHITECTURE.md "Elastic trial fabric"). Validated here:
        # a malformed report must be an immediate 400 the agent can act
        # on, not a 500 it burns its whole register-retry budget against.
        n_devices = body.get("n_devices")
        if n_devices is not None:
            try:
                n_devices = int(n_devices)
            except (TypeError, ValueError):
                raise BadRequest(
                    f"n_devices must be an integer, got {n_devices!r}"
                )
        mesh_shape = body.get("mesh_shape")
        if mesh_shape is not None:
            try:
                mesh_shape = {
                    str(k): int(v) for k, v in mesh_shape.items()
                }
            except (TypeError, ValueError, AttributeError):
                raise BadRequest(
                    "mesh_shape must be an object of integer axis sizes, "
                    f"got {mesh_shape!r}"
                )
        wid = _cluster_or_400().register_remote(
            body.get("mem_capacity_mb"),
            n_devices=n_devices,
            mesh_shape=mesh_shape,
        )
        resp = {"worker_id": wid}
        try:
            # predictor-driven AOT prewarm hints (docs/ARCHITECTURE.md
            # "Data-plane caching and prewarm"): hot job shapes the new
            # worker should warm in the background before first placement
            hints = coord.prewarm_hints()
        except Exception:  # noqa: BLE001 — hints are advisory, never
            # allowed to fail a registration
            hints = []
        if hints:
            resp["prewarm"] = hints
        return _json(resp, status=201)

    def unsubscribe(request, wid):
        _cluster_or_400().unregister_remote(wid)
        return _json({"status": "ok"})

    def heartbeat(request, wid):
        ok = _cluster_or_400().engine.heartbeat(wid)
        return _json({"status": "ok" if ok else "unknown_worker"}, status=200 if ok else 404)

    def next_tasks(request, wid):
        cluster = _cluster_or_400()
        max_n = int(request.args.get("max", 64))
        timeout_s = float(request.args.get("timeout", 10.0))
        out = {"tasks": cluster.pull_tasks(wid, max_n, timeout_s)}
        # cooperative-cancel list (docs/SEARCH.md): attempts the rung
        # controller pruned mid-flight — the agent feeds them to its
        # executor, which stops each at the next batch boundary and posts
        # a terminal ``pruned`` result
        cancels = cluster.cancel_list()
        if cancels:
            out["cancel"] = cancels
        return _json(out)

    def task_result(request, wid):
        _cluster_or_400().push_result(wid, request.get_json(force=True))
        return _json({"status": "ok"})

    def task_metrics(request, wid):
        _cluster_or_400().push_metrics(wid, request.get_json(force=True))
        return _json({"status": "ok"})

    def dataset(request, dataset_id):
        """Serve the coordinator's staged CSV (preprocessed preferred) so
        remote agents can fetch-on-miss (FetchingDatasetCache). ``?probe=1``
        returns only the staged kind (cheap freshness check — agents probe
        before downloading)."""
        from werkzeug.wsgi import wrap_file

        from ..data.datasets import find_csv

        root = coord.config.storage.datasets_dir
        path = find_csv(dataset_id, preprocessed=True, root=root)
        kind = "preprocessed"
        if path is None:
            path = find_csv(dataset_id, root=root)
            kind = "raw"
        if path is None:
            return _json(
                {"status": "error", "message": f"dataset {dataset_id!r} not staged"},
                status=404,
            )
        if request.args.get("probe"):
            return _json({"kind": kind, "size": __import__("os").path.getsize(path)})
        # streamed, not read into memory: N agents cold-starting on a
        # 100 MB dataset must not allocate N full copies coordinator-side
        return Response(
            wrap_file(request.environ, open(path, "rb")),
            mimetype="text/csv",
            direct_passthrough=True,
            headers={
                "X-Dataset-Kind": kind,
                "Content-Disposition": f"attachment; filename={dataset_id}.csv",
            },
        )

    def slice_heartbeat(request, slice_id, rank):
        now = _time.time()
        with _slices_lock:
            _slices.setdefault(slice_id, {})[int(rank)] = now
            # prune slices whose every rank went silent (crash-looping
            # slices mint a fresh uuid per restart — without a sweep the
            # table grows one dead dict per restart forever)
            for sid in [
                s for s, ranks in _slices.items()
                if s != slice_id and ranks
                and now - max(ranks.values()) > 900
            ]:
                del _slices[sid]
        return _json({"status": "ok"})

    def slice_status(request, slice_id):
        now = _time.time()
        with _slices_lock:
            ranks = dict(_slices.get(slice_id, {}))
        return _json({
            "ranks": {str(r): round(now - ts, 3) for r, ts in ranks.items()}
        })

    def migrate_in(request):
        """Peer-to-peer job handoff ingest (docs/ROBUSTNESS.md "Shard
        rebalancing"): a hot donor shard POSTs a quiesced job's full
        record here. The recipient journals ``migrate_in`` BEFORE the
        donor journals its forwarding stamp, so a crash between the two
        duplicates ownership (deduped by attempt fencing) rather than
        losing the job. Idempotent: a duplicate export is re-accepted."""
        body = request.get_json(force=True, silent=True) or {}
        try:
            return _json(coord.migrate_in(body))
        except ValueError as e:
            return _json({"status": "error", "message": str(e)}, status=400)

    def steal_candidates(request):
        """Queued subtasks this shard would surrender to an idle peer
        (work stealing). Empty unless rebalancing is enabled AND the
        local shard_pressure is over the hot threshold — a busy-but-
        coping shard keeps its queue."""
        return _json(coord.steal_candidates())

    def steal_tasks(request):
        """Grant endpoint for work stealing: the thief POSTs
        ``{"thief_shard": k, "max_n": n}`` and receives fenced task
        attempts (fresh attempt number, donor-side tombstone journaled)
        it may run locally. Results flow back via /peer_result."""
        body = request.get_json(force=True, silent=True) or {}
        try:
            thief = int(body.get("thief_shard", -1))
            max_n = int(body.get("max_n", coord.config.service.steal_max_tasks))
            # mesh-aware stealing (optional, backward-compatible): the
            # thief's widest idle slice caps the priced candidate width
            max_nd = body.get("max_n_devices")
            max_nd = int(max_nd) if max_nd is not None else None
        except (TypeError, ValueError):
            from werkzeug.exceptions import BadRequest

            raise BadRequest(
                "thief_shard, max_n and max_n_devices must be integers"
            )
        return _json({"tasks": coord.release_for_steal(
            thief, max_n,
            max_n_devices=max_nd,
            prefer_wide=bool(body.get("prefer_wide")),
        )})

    def peer_result(request):
        """Result relay from a peer shard: forwarded late results from a
        migration donor, or stolen-task results from a thief. Each result
        is published onto the local bus exactly as a worker result would
        be — the ingest loop's dedup/staleness rules apply unchanged."""
        body = request.get_json(force=True, silent=True) or {}
        results = body.get("results")
        if results is None:
            results = [body]
        n = 0
        for r in results:
            if isinstance(r, dict) and r.get("subtask_id"):
                coord.ingest_peer_result(r)
                n += 1
        return _json({"status": "ok", "ingested": n})

    handlers = locals()

    # CORS parity with the reference master's flask-cors default config
    # (allow-all; master.py:20-24): browser dashboards may call the API
    # cross-origin, including OPTIONS preflights
    _cors = {
        "Access-Control-Allow-Origin": "*",
        "Access-Control-Allow-Headers": "Content-Type, Authorization",
        "Access-Control-Allow-Methods": "GET, POST, OPTIONS",
    }

    @Request.application
    def app(request):
        if request.method == "OPTIONS":
            return Response(status=204, headers=_cors)
        # trace middleware: an inbound X-Trace-Id activates that trace for
        # the handler (contextvar), so spans opened inside — including the
        # coordinator's job.submit — join the CLIENT's trace; the id is
        # echoed on the response. Untraced requests open no span at all
        # (a /health poll must not mint garbage traces).
        trace_id = request.headers.get(TRACE_HEADER)
        # RED middleware (docs/OBSERVABILITY.md "Perf observatory"): every
        # request lands in tpuml_http_request_seconds{route,method,code}.
        # Routes label by ENDPOINT name (bounded cardinality — path params
        # never become label values); unmatched paths pool under one
        # "unmatched" cell. Streaming (SSE) responses record time to the
        # response object — the submit latency; delivery lag has its own
        # gauge (tpuml_sse_lag_seconds).
        t0 = _time.perf_counter()
        endpoint = None
        try:
            endpoint, values = url_map.bind_to_environ(request.environ).match()
            counter_inc("tpuml_http_requests_total", endpoint=endpoint)
            # trace_spans is the span TRANSPORT — tracing it would append
            # one meta-span to every shipped batch's timeline
            if trace_id and endpoint != "trace_spans" and obs_enabled():
                # X-Parent-Span: a front end sends its open frontend.proxy
                # span id so this hop's span nests under it — the stitch
                # that makes the proxy span the trace's single root
                parent_id = request.headers.get(PARENT_HEADER)
                with activate(trace_id, parent_id):
                    with span(f"http.{endpoint}", trace_id=trace_id):
                        resp = handlers[endpoint](request, **values)
            else:
                resp = handlers[endpoint](request, **values)
        except NotFound:
            resp = _json({"status": "error", "message": "not found"}, status=404)
        except HTTPException as e:
            resp = _json({"status": "error", "message": str(e)}, status=e.code or 500)
        except (KeyError, FileNotFoundError) as e:
            resp = _json({"status": "error", "message": str(e)}, status=404)
        except Exception as e:  # noqa: BLE001
            resp = _json({"status": "error", "message": str(e)}, status=500)
        observe(
            "tpuml_http_request_seconds",
            _time.perf_counter() - t0,
            route=endpoint or "unmatched",
            method=request.method,
            code=str(resp.status_code),
        )
        resp.headers.extend(_cors)
        if trace_id:
            resp.headers[TRACE_HEADER] = trace_id
        return resp

    app.coordinator = coord
    return app


def serve(coordinator: Optional[Coordinator] = None, host: Optional[str] = None, port: Optional[int] = None):
    from werkzeug.serving import run_simple

    from ..utils.config import get_config

    cfg = get_config().service
    app = create_app(coordinator)
    run_simple(host or cfg.host, port or cfg.port, app, threaded=True)


def main() -> None:
    """``tpuml-coordinator`` console entry point: serve the REST surface.

    - ``--cluster`` (default): scheduler-mediated dispatch — remote agents
      register over /subscribe; optionally ``--local-executors N`` adds
      in-process workers so the box serves jobs with no agents attached, or
      ``--agent-executors N`` runs them as supervised child processes
      (device-fault containment: a poisoned backend kills only the child,
      tasks requeue, the supervisor respawns — runtime/supervisor.py).
    - ``--direct``: single in-process executor, no placement engine (the
      laptop / single-TPU-VM mode).
    The compose analog: reference docker-compose.yml:86-131 (master +
    scheduler services collapsed into this one process).
    """
    import argparse

    parser = argparse.ArgumentParser(description="tpuml coordinator server")
    parser.add_argument("--host", default=None)
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--direct", action="store_true",
                        help="in-process executor, no placement engine")
    parser.add_argument("--local-executors", type=int, default=0, metavar="N",
                        help="cluster mode: also attach N in-process executors")
    parser.add_argument("--agent-executors", type=int, default=0, metavar="N",
                        help="cluster mode: run N supervised child agent "
                             "processes (fault-isolated executors)")
    parser.add_argument("--journal", action="store_true",
                        help="journal job state; resume in-flight jobs on restart")
    # sharded control plane (docs/ARCHITECTURE.md "Sharded control
    # plane"): this process serves ONE shard of an N-shard fleet behind
    # stateless front ends (runtime/frontend.py). Job/worker ids get the
    # s<k>- stamp, the journal moves to <journal_dir>/shard-<k> (the
    # hot-standby takeover unit), and the GLOBAL admission caps are
    # carved into per-shard shares so the fleet-wide accepted load stays
    # bounded by the configured totals.
    parser.add_argument("--shard-index", type=int, default=None, metavar="K",
                        help="serve shard K of a sharded control plane")
    parser.add_argument("--num-shards", type=int, default=1, metavar="N",
                        help="total shards in the fleet (with --shard-index)")
    # rebalancing peer directory: base URLs of EVERY shard (index == list
    # position, including this one — it is skipped when dialing). Static
    # because ShardFleet allocates ports before any shard starts; action
    # is still gated on service.rebalance_enabled.
    parser.add_argument("--peers", default=None, metavar="URL,URL,...",
                        help="comma-separated shard base URLs for "
                             "cross-shard migration / work stealing")
    args = parser.parse_args()
    if args.direct and args.agent_executors > 0:
        parser.error("--agent-executors requires cluster mode (drop --direct)")
    if args.shard_index is not None and not (
        0 <= args.shard_index < max(args.num_shards, 1)
    ):
        parser.error("--shard-index must be in [0, --num-shards)")
    if args.num_shards > 100:
        # the 2-digit s<k>- stamp grammar bounds the fleet (sharding.py
        # MAX_SHARDS); fail at launch, not at first unroutable id
        parser.error("--num-shards is capped at 100 by the id stamp grammar")
    if args.shard_index is not None and args.direct:
        parser.error("--shard-index requires cluster mode (drop --direct)")

    supervisor = None
    slot_envs = None
    if args.agent_executors > 0:
        import os as _os

        # single-accelerator host policy: exactly one process may own the
        # chip. The parent pins itself to CPU and agent slot 0 inherits the
        # original platform — unless --local-executors run in the parent,
        # which then keeps the chip and every child slot pins to CPU. This
        # MUST happen before Coordinator() below: its eager artifact-refit
        # executor latches the platform via setup_jax on construction.
        chip_taken = args.local_executors > 0
        inherit = {"TPUML_PLATFORM": _os.environ.get("TPUML_PLATFORM")}
        if not chip_taken:
            _os.environ["TPUML_PLATFORM"] = "cpu"
        slot_envs = [
            inherit if (i == 0 and not chip_taken)
            else {"TPUML_PLATFORM": "cpu"}
            for i in range(args.agent_executors)
        ]

    if args.direct:
        coord = Coordinator(journal=args.journal)
    else:
        from .cluster import ClusterRuntime

        shard_kwargs = {}
        if args.shard_index is not None:
            import os as _os

            from ..utils.config import get_config as _cfg
            from .sharding import shard_service_config

            cfg = shard_service_config(_cfg(), args.num_shards)
            shard_kwargs = {
                "config": cfg,
                "shard_id": args.shard_index,
                "n_shards": args.num_shards,
                "journal_dir": _os.path.join(
                    cfg.storage.journal_dir, f"shard-{args.shard_index}"
                ),
            }
        cluster = ClusterRuntime(shard_id=args.shard_index)
        for _ in range(max(args.local_executors, 0)):
            cluster.add_executor()
        coord = Coordinator(
            cluster=cluster, journal=args.journal, **shard_kwargs
        )
        if args.peers:
            coord.peer_urls = [
                u.strip().rstrip("/")
                for u in args.peers.split(",") if u.strip()
            ]
        if args.agent_executors > 0:
            from ..utils.config import get_config as _cfg
            from .supervisor import AgentSupervisor, agent_command

            cfg = _cfg().service
            # children must dial an address the bound server answers on:
            # wildcard binds answer loopback, a specific --host only itself
            host = args.host or cfg.host
            dial = "127.0.0.1" if host in (None, "", "0.0.0.0", "::") else host
            url = f"http://{dial}:{args.port or cfg.port}"
            supervisor = AgentSupervisor(
                agent_command(url), n=args.agent_executors,
                slot_envs=slot_envs,
            )
            supervisor.start()
            coord.agent_supervisor = supervisor
    try:
        serve(coord, host=args.host, port=args.port)
    finally:
        if supervisor is not None:
            supervisor.stop()


if __name__ == "__main__":
    main()
