#!/usr/bin/env bash
# One-command local fleet: coordinator server + N worker agents as real OS
# processes — the no-docker equivalent of deploy/compose.yaml (and of the
# reference's `docker-compose up`, minus Kafka/ZooKeeper/Redis).
#
#   deploy/launch_fleet.sh up [N_AGENTS=2] [PORT=5001]   # start + health-wait
#   deploy/launch_fleet.sh demo                          # run the titanic demo
#   deploy/launch_fleet.sh status                        # health plane snapshot
#   deploy/launch_fleet.sh down                          # stop everything
#
# State (pids/logs) lives in .fleet/ under the repo root.

set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
STATE="$REPO/.fleet"
PORT="${PORT:-5001}"
PY="${PYTHON:-python}"

up() {
  local n_agents="${1:-2}"
  mkdir -p "$STATE"
  # A chip belongs to ONE process (the second to initialize the backend
  # dies). Same policy as `tpuml-coordinator --agent-executors`
  # (runtime/server.py): the coordinator and every agent but the first pin
  # themselves to the host CPU; agent 1 inherits the platform and owns the
  # chip. On a multi-chip host run one fleet per chip, or one agent with
  # --distributed over all of them.
  echo "starting coordinator on :$PORT (TPUML_PLATFORM=cpu) ..."
  (cd "$REPO" && TPUML_PLATFORM=cpu PYTHONPATH="$REPO" nohup "$PY" -m \
      cs230_distributed_machine_learning_tpu.runtime.server \
      --host 127.0.0.1 --port "$PORT" --journal \
      > "$STATE/coordinator.log" 2>&1 & echo $! > "$STATE/coordinator.pid")
  for _ in $(seq 1 120); do
    if curl -fsS "$URL/health" > /dev/null 2>&1; then break; fi
    sleep 0.5
  done
  curl -fsS "$URL/health" > /dev/null || {
    echo "coordinator failed to come up; see $STATE/coordinator.log"; exit 1; }
  for i in $(seq 1 "$n_agents"); do
    local plat="${TPUML_PLATFORM:-}"
    [ "$i" -gt 1 ] && plat="cpu"
    echo "starting agent $i (TPUML_PLATFORM=${plat:-inherited}) ..."
    (cd "$REPO" && TPUML_PLATFORM="$plat" PYTHONPATH="$REPO" nohup "$PY" -m \
        cs230_distributed_machine_learning_tpu.runtime.agent --url "$URL" \
        > "$STATE/agent$i.log" 2>&1 & echo $! > "$STATE/agent$i.pid")
  done
  # wait until every agent registered
  for _ in $(seq 1 120); do
    n_reg="$(curl -fsS "$URL/workers" | "$PY" -c \
        'import json,sys; print(len(json.load(sys.stdin)))' 2>/dev/null || echo 0)"
    [ "$n_reg" -ge "$n_agents" ] && break
    sleep 0.5
  done
  echo "fleet up: coordinator :$PORT + $n_reg agents (logs in $STATE/)"
}

demo() {
  (cd "$REPO" && PYTHONPATH="$REPO" "$PY" examples/demo_end_to_end.py --url "$URL")
}

# health-plane snapshot (docs/OBSERVABILITY.md "Fleet health plane"):
# firing alerts + the capacity signal an external autoscaler would read
status() {
  curl -fsS "$URL/alerts" | "$PY" -c '
import json, sys
b = json.load(sys.stdin)
firing = b.get("firing") or []
msg = "alerts: " + str(b["status"])
if firing:
    msg += " (%d firing: %s)" % (len(firing), firing)
print(msg)
'
  curl -fsS "$URL/autoscale" | "$PY" -c '
import json, sys
b = json.load(sys.stdin)
s = b["signals"]
print("autoscale: desired_workers=%s live_workers=%s backlog_s=%s pressure=%s"
      % (b["desired_workers"], b["live_workers"],
         s["backlog_seconds"], s["pressure"]))
'
}

down() {
  for f in "$STATE"/*.pid; do
    [ -e "$f" ] || continue
    kill "$(cat "$f")" 2>/dev/null || true
    rm -f "$f"
  done
  # belt-and-braces: pid files miss processes from a superseded `up` run
  pkill -f "cs230_distributed_machine_learning_tpu.runtime.server .*--port $PORT" 2>/dev/null || true
  pkill -f "cs230_distributed_machine_learning_tpu.runtime.agent --url $URL" 2>/dev/null || true
  echo "fleet stopped"
}

case "${1:-up}" in
  up)    PORT="${3:-$PORT}"; URL="http://127.0.0.1:${PORT}"; up "${2:-2}" ;;
  demo)   PORT="${2:-$PORT}"; URL="http://127.0.0.1:${PORT}"; demo ;;
  status) PORT="${2:-$PORT}"; URL="http://127.0.0.1:${PORT}"; status ;;
  down)   PORT="${2:-$PORT}"; URL="http://127.0.0.1:${PORT}"; down ;;
  *) echo "usage: $0 {up [n_agents] [port]|demo [port]|status [port]|down [port]}"; exit 2 ;;
esac
