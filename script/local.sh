#!/usr/bin/env bash
# Launch an N-process parameter_server_tpu job on ONE machine. CPU only:
# every process is forced onto a virtual CPU mesh, because a chip belongs
# to one process at a time — N processes on one box cannot share it.
#
# TPU-native counterpart of the reference's script/local.sh (which starts
# a scheduler + S servers + W workers as local processes): here every
# process is a "host" joined via jax.distributed (process 0 doubles as the
# coordinator, the reference's scheduler), and server/worker roles are
# mesh AXES inside the SPMD program, not separate processes.
#
# Usage:
#   script/local.sh <num_hosts> <command...>
# e.g.
#   script/local.sh 2 python -m parameter_server_tpu.apps.linear.main \
#       conf.conf --num-servers 2
#
# Env knobs:
#   PS_LOCAL_DEVICES  virtual CPU devices per process (default 2)
#   PS_PORT           coordinator port (default: random free-ish)
#
# On a real multi-host TPU pod, run the same command on every host with
# PS_COORDINATOR_ADDRESS=<host0>:<port> PS_NUM_PROCESSES=<N>
# PS_PROCESS_ID=<i> set by your cluster launcher (srun/mpirun/k8s), and
# leave JAX_PLATFORMS alone so the TPU plugin provides the devices.
set -euo pipefail
N=${1:?usage: local.sh <num_hosts> <command...>}; shift
PORT=${PS_PORT:-$(( (RANDOM % 20000) + 20000 ))}
DEVS=${PS_LOCAL_DEVICES:-2}
ROOT=$(cd "$(dirname "$0")/.." && pwd)
export PYTHONPATH="${ROOT}${PYTHONPATH:+:$PYTHONPATH}"

pids=()
cleanup() { kill "${pids[@]}" 2>/dev/null || true; }
trap cleanup INT TERM

for ((i = N - 1; i >= 0; i--)); do
  env \
    JAX_PLATFORMS=cpu \
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${DEVS}" \
    PS_COORDINATOR_ADDRESS="127.0.0.1:${PORT}" \
    PS_NUM_PROCESSES="$N" \
    PS_PROCESS_ID="$i" \
    "$@" &
  pids+=($!)
done

# fail fast: if any child exits nonzero, kill the siblings instead of
# letting them block in the rendezvous until the coordinator timeout.
# wait -n -p disambiguates "no children left" from a child that itself
# exited 127 (command not found): 127 with no reaped pid = drained.
rc=0
remaining=${#pids[@]}
while (( remaining > 0 )); do
  r=0
  reaped=""
  wait -n -p reaped "${pids[@]}" 2>/dev/null || r=$?
  if [[ -z ${reaped} ]]; then break; fi  # set drained
  remaining=$((remaining - 1))
  if (( r != 0 )); then
    if (( rc == 0 )); then rc=$r; fi   # keep the FIRST failure, not SIGTERMs
    cleanup
  fi
done
exit "$rc"
