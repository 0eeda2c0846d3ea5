#!/bin/bash
# Watch a run from the outside.
#
# Two modes, picked by argument (ISSUE 4 satellite):
#
#   tpu_watch.sh --metrics HOST:PORT [--interval N]
#       Poll the live telemetry endpoints (TrainConfig.metrics_port,
#       telemetry/serve.py): each tick prints /health (watchdog phase,
#       stall age, 503 = stalled), the /window summary (step, loss,
#       step-time p50), and any /fleet straggler verdict. Waits
#       patiently while the endpoint has never answered (the run may
#       not have bound the port yet); once it HAS been up, a dead
#       endpoint means the run ended (the server closes on every exit
#       path, usually before the next poll can observe the final
#       window) — fall back to the workdir file tail when --workdir is
#       also given for the definitive verdict, else exit 0 when the
#       last health probe was healthy (normal end; the exact exit
#       reason lives in the run dir) or 2 when it was stalled (the run
#       likely died — watchdog fatal, crash).
#
#   tpu_watch.sh --workdir DIR [--interval N]
#       File-tail fallback for runs without a metrics port: print the
#       last line of DIR/telemetry/metrics.jsonl each tick, exit 0 on a
#       final line.
#
# METRICS_ADDR=HOST:PORT in the environment implies --metrics.
set -u

interval=10
metrics_addr="${METRICS_ADDR:-}"
workdir=""

while [ $# -gt 0 ]; do
  case "$1" in
    --metrics) metrics_addr="$2"; shift 2 ;;
    --workdir) workdir="$2"; shift 2 ;;
    --interval) interval="$2"; shift 2 ;;
    *) echo "usage: tpu_watch.sh [--metrics HOST:PORT] [--workdir DIR] [--interval N]" >&2; exit 64 ;;
  esac
done

# One JSONL line on stdin -> a one-line human summary. Prints FINAL on
# its own line first when the run ended (the caller's exit signal).
SUMMARIZE_PY='
import json, sys
try:
    line = json.loads(sys.stdin.read())
except Exception:
    sys.exit(1)
if "kind" not in line:
    sys.exit(1)  # the 404 {"error": ...} body pre-first-window
kind = line.get("kind", "?")
if kind == "final":
    print("FINAL")
d = line.get("derived") or {}
m = line.get("metrics") or {}
parts = ["step %s" % line.get("step"), "kind=%s" % kind]
if kind == "final":
    parts.append("exit=%s" % line.get("exit_reason"))
loss = m.get("train/loss")
if loss is not None:
    parts.append("loss=%.4f" % loss)
p50 = d.get("step_time_p50")
if p50 is not None:
    parts.append("p50=%.1fms" % (p50 * 1e3))
eps = d.get("examples_per_sec")
if eps is not None:
    parts.append("%.0f ex/s" % eps)
fleet = line.get("fleet") or {}
if fleet.get("straggler"):
    parts.append("STRAGGLER host %s %.1fx %s-side" % (
        fleet.get("slowest_host"), fleet.get("skew") or 0.0,
        fleet.get("side")))
print(" ".join(parts))
'

summarize_window() {
  python -c "$SUMMARIZE_PY"
}

if [ -n "$metrics_addr" ]; then
  # ---- live-endpoint mode (metrics_port is set on the run) ----
  base="http://$metrics_addr"
  echo "watching $base (interval ${interval}s)"
  seen_up=0
  last_ok=1
  down_count=0
  while true; do
    # -s without -f: a 503 (stalled) still carries a JSON body we want.
    health=$(curl -s --max-time 5 "$base/health" 2>/dev/null)
    if [ -z "$health" ]; then
      down_count=$((down_count + 1))
      if [ "$seen_up" = 1 ] && [ "$down_count" -lt 2 ]; then
        # One empty probe can be a transient blip (busy host, curl
        # timeout) — only consecutive failures mean the port is gone.
        echo "$(date -u +%H:%M:%S) health probe failed (retrying)"
        sleep "$interval"; continue
      fi
      if [ "$seen_up" = 1 ]; then
        # The server closes on every exit path, usually milliseconds
        # after the final window — a now-dead endpoint IS the end
        # signal; don't poll a closed port forever. The final window
        # itself is almost never observable from here (emitted and the
        # port closed between two polls), so the verdict comes from
        # the file tail when we have one, else from the last health
        # probe: healthy-then-gone = normal end, stalled-then-gone =
        # the run likely died.
        echo "$(date -u +%H:%M:%S) endpoint gone: run ended"
        if [ -n "$workdir" ]; then break; fi  # file tail has the verdict
        echo "exit reason is in the run dir (tools/telemetry_report.py <rundir>)"
        if [ "$last_ok" = 1 ]; then exit 0; fi
        echo "last health probe was STALLED — the run likely died" >&2
        exit 2
      fi
      # Never came up but the run is already writing telemetry: the
      # bind likely failed (loop.py survives a taken port and trains
      # on) — the file tail is the only view we will ever get. A few
      # ticks of grace first: a resumed run has an old metrics.jsonl
      # on disk while the new process is still starting up.
      if [ -n "$workdir" ] && [ "$down_count" -ge 6 ] \
          && [ -f "$workdir/telemetry/metrics.jsonl" ]; then
        echo "$(date -u +%H:%M:%S) endpoint never came up but telemetry exists: falling back to the file tail"
        break
      fi
      echo "$(date -u +%H:%M:%S) endpoint not up yet (run not started?)"
      sleep "$interval"; continue
    fi
    seen_up=1
    down_count=0
    case "$health" in *'"ok": true'*) last_ok=1 ;; *) last_ok=0 ;; esac
    window=$(curl -s --max-time 5 "$base/window" 2>/dev/null)
    summary=$(printf '%s' "$window" | summarize_window)
    echo "$(date -u +%H:%M:%S) health: $health"
    [ -n "$summary" ] && echo "$(date -u +%H:%M:%S) window: $(printf '%s\n' "$summary" | tail -1)"
    fleet=$(curl -s --max-time 5 "$base/fleet" 2>/dev/null | summarize_window | grep -o 'STRAGGLER.*')
    [ -n "$fleet" ] && echo "$(date -u +%H:%M:%S) fleet:  $fleet"
    if printf '%s\n' "$summary" | grep -q '^FINAL$'; then
      echo "run ended"; exit 0
    fi
    sleep "$interval"
  done
fi

if [ -n "$workdir" ]; then
  # ---- file-tail fallback ----
  jsonl="$workdir/telemetry/metrics.jsonl"
  echo "tailing $jsonl (interval ${interval}s)"
  while true; do
    if [ -f "$jsonl" ]; then
      summary=$(tail -1 "$jsonl" | summarize_window)
      [ -n "$summary" ] && echo "$(date -u +%H:%M:%S) $(printf '%s\n' "$summary" | tail -1)"
      if printf '%s\n' "$summary" | grep -q '^FINAL$'; then
        echo "run ended"; exit 0
      fi
    else
      echo "$(date -u +%H:%M:%S) no telemetry yet"
    fi
    sleep "$interval"
  done
fi

echo "usage: tpu_watch.sh [--metrics HOST:PORT] [--workdir DIR] [--interval N]" >&2
exit 64
