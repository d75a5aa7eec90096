#!/usr/bin/env bash
# Local multi-controller launcher: run N copies of a repro.launch CLI as
# N simulated hosts (one process per "host", K fake CPU devices each via
# --xla_force_host_platform_device_count), wired together through a
# jax.distributed coordinator on localhost. A CPU simulation only: on a
# TPU host a chip belongs to one process, and one process drives them all.
#
#   scripts/launch_multihost.sh [-n NPROC] [-d DEV_PER_PROC] [-p PORT] \
#       [-m MODULE] [-l LOGDIR] -- <args passed to every process>
#
#   # 2-host stream training over a shared shard directory:
#   scripts/launch_multihost.sh -n 2 -- \
#       --dataset covtype --scale 0.005 --m 64 --plan stream \
#       --data-dir /tmp/mh_shards --export-chunks --save /tmp/mh.npz
#
#   # then serve that checkpoint from a 2-process spanning engine:
#   scripts/launch_multihost.sh -n 2 -m repro.launch.kernel_serve -- \
#       --ckpt /tmp/mh.npz --requests 16 --max-batch 64
#
# The watchdog kills every remaining worker the moment one dies, prints
# the dead worker's exit code and log tail, and exits nonzero — a hung
# collective can never outlive its peers silently. Process 0's log is
# echoed on success (followers are silent by design).
set -u

NPROC=2
DEVS=1
PORT=$(( (RANDOM % 2000) + 12000 ))
MODULE=repro.launch.kernel_train
LOGDIR=""
while getopts "n:d:p:m:l:h" opt; do
  case "$opt" in
    n) NPROC="$OPTARG" ;;
    d) DEVS="$OPTARG" ;;
    p) PORT="$OPTARG" ;;
    m) MODULE="$OPTARG" ;;
    l) LOGDIR="$OPTARG" ;;
    h) sed -n '2,20p' "$0"; exit 0 ;;
    *) exit 2 ;;
  esac
done
shift $((OPTIND - 1))
[ "${1:-}" = "--" ] && shift

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
LOGDIR="${LOGDIR:-$(mktemp -d /tmp/multihost-logs.XXXXXX)}"
mkdir -p "$LOGDIR"
echo "[launch] $MODULE x $NPROC processes ($DEVS fake devices each), " \
     "coordinator 127.0.0.1:$PORT, logs in $LOGDIR"

PIDS=()
for ((p = 0; p < NPROC; p++)); do
  XLA_FLAGS="--xla_force_host_platform_device_count=$DEVS ${XLA_FLAGS:-}" \
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  PYTHONPATH="$ROOT/src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m "$MODULE" \
      --coordinator "127.0.0.1:$PORT" --num-processes "$NPROC" \
      --process-id "$p" "$@" > "$LOGDIR/proc$p.log" 2>&1 &
  PIDS[$p]=$!
done

# Watchdog: poll the fleet; first nonzero exit kills the rest.
FAIL=""
ALIVE=$NPROC
while [ "$ALIVE" -gt 0 ] && [ -z "$FAIL" ]; do
  ALIVE=0
  for ((p = 0; p < NPROC; p++)); do
    pid="${PIDS[$p]}"
    [ -z "$pid" ] && continue
    if kill -0 "$pid" 2>/dev/null; then
      ALIVE=$((ALIVE + 1))
    else
      wait "$pid"; rc=$?
      PIDS[$p]=""
      if [ "$rc" -ne 0 ]; then FAIL="$p:$rc"; fi
    fi
  done
  [ "$ALIVE" -gt 0 ] && [ -z "$FAIL" ] && sleep 0.2
done

if [ -n "$FAIL" ]; then
  DEAD="${FAIL%%:*}"; RC="${FAIL##*:}"
  for ((p = 0; p < NPROC; p++)); do
    [ -n "${PIDS[$p]}" ] && kill -9 "${PIDS[$p]}" 2>/dev/null
  done
  wait 2>/dev/null
  echo "[launch] FAIL: process $DEAD exited rc=$RC — killed the remaining" \
       "workers. Its log tail ($LOGDIR/proc$DEAD.log):" >&2
  tail -n 25 "$LOGDIR/proc$DEAD.log" >&2
  exit 1
fi
wait 2>/dev/null

echo "[launch] OK — process 0 output:"
cat "$LOGDIR/proc0.log"
