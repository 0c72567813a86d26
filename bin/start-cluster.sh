#!/usr/bin/env bash
# Local cluster: one JobManager + TaskManagers on this host — the analogue of
# the reference's bin/start-cluster.sh.
#
# A chip belongs to one process at a time, so the JobManager is pinned to the
# CPU backend and there is ONE TaskManager per visible TPU chip, each pinned
# to its own chip (libtpu's single-chip process bounds). With no chip visible
# a single TaskManager runs on whatever backend JAX_PLATFORMS names.
# Usage: start-cluster.sh [N_TASKMANAGERS]   (default: one per chip)
set -euo pipefail
cd "$(dirname "$0")/.."
CHIPS="$( (ls -d /dev/accel[0-9]* /dev/vfio/[0-9]* 2>/dev/null || true) | wc -l)"
N_TM="${1:-$(( CHIPS > 0 ? CHIPS : 1 ))}"
if [ "$CHIPS" -gt 0 ] && [ "$N_TM" -gt "$CHIPS" ]; then
  echo "start-cluster: $N_TM taskmanagers asked for but $CHIPS chip(s) visible;" \
       "a second process on a chip fails or hangs" >&2
  exit 1
fi
PORT="${FLINK_TPU_JM_PORT:-6123}"
LOGDIR="${FLINK_TPU_LOG_DIR:-/tmp/flink_tpu_logs}"
mkdir -p "$LOGDIR"
JAX_PLATFORMS=cpu python -m flink_tpu.runtime.cluster jobmanager --port "$PORT" \
  --checkpoint-dir "${FLINK_TPU_CHECKPOINT_DIR:-/tmp/flink_tpu_checkpoints}" \
  --checkpoint-interval "${FLINK_TPU_CHECKPOINT_INTERVAL:-10}" \
  > "$LOGDIR/jobmanager.log" 2>&1 &
echo $! > "$LOGDIR/jobmanager.pid"
sleep 1
: > "$LOGDIR/taskmanagers.pid"
for i in $(seq 0 $((N_TM - 1))); do
  if [ "$CHIPS" -gt 1 ]; then
    # one chip, one process: chip $i only, and a mesh-controller port of its
    # own so the per-process runtimes do not collide (a host with a single
    # visible chip needs no pinning: its one TaskManager takes it)
    pin=(env TPU_VISIBLE_DEVICES="$i" TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1
         TPU_PROCESS_BOUNDS=1,1,1
         TPU_MESH_CONTROLLER_ADDRESS="localhost:$((8476 + i))"
         TPU_MESH_CONTROLLER_PORT="$((8476 + i))")
  else
    pin=(env)
  fi
  "${pin[@]}" python -m flink_tpu.runtime.cluster taskmanager \
    --jobmanager "127.0.0.1:$PORT" \
    > "$LOGDIR/taskmanager-$((i + 1)).log" 2>&1 &
  echo $! >> "$LOGDIR/taskmanagers.pid"
done
echo "cluster up: jobmanager 127.0.0.1:$PORT (cpu), $N_TM taskmanager(s)," \
     "$CHIPS chip(s) visible (logs in $LOGDIR)"
