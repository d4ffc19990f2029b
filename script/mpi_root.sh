#!/usr/bin/env bash
# Cluster launcher: start an N-process parameter_server_tpu job through
# mpirun (OpenMPI/MPICH) with mpi_node.sh adapting each rank into the
# framework's env contract. TPU-native counterpart of the reference's
# script/mpi_root.sh (which computed the scheduler node and mpirun'd
# mpi_node.sh across a hostfile).
#
# Usage:
#   script/mpi_root.sh <N> <command...>
# e.g.
#   script/mpi_root.sh 4 python -m parameter_server_tpu.apps.lm.main \
#       --steps 100 --fsdp
#
# Env knobs:
#   PS_HOSTFILE  passed to mpirun -hostfile (multi-machine runs); the
#                FIRST host in it must be reachable from every rank —
#                it becomes the jax.distributed coordinator
#   PS_PORT      coordinator port (default: 29431)
#   PS_MPIRUN    mpirun binary (default: mpirun from PATH)
#
# Without any MPI runtime on PATH the launcher falls back to N local
# processes with emulated ranks — same code path through mpi_node.sh,
# so CI exercises the launcher without an MPI install. Local fallback
# and single-host mpirun are CPU only: both force a CPU device mesh per
# process (PS_LOCAL_DEVICES, default 2), mirroring local.sh, because a
# chip belongs to one process at a time. On a real pod (PS_HOSTFILE)
# the TPU runtime provides devices and JAX_PLATFORMS is left alone.
set -euo pipefail
N=${1:?usage: mpi_root.sh <N> <command...>}; shift
PORT=${PS_PORT:-29431}
MPIRUN=${PS_MPIRUN:-mpirun}
DIR=$(cd "$(dirname "$0")" && pwd)

if command -v "${MPIRUN}" >/dev/null 2>&1; then
  if [[ -n ${PS_HOSTFILE:-} ]]; then
    # multi-machine: leave the device platform alone (a real pod's TPU
    # plugin provides devices); first host doubles as coordinator
    host=$(awk 'NF && $1 !~ /^#/ {print $1; exit}' "${PS_HOSTFILE}")
    exec "${MPIRUN}" -hostfile "${PS_HOSTFILE}" -np "${N}" \
      "${DIR}/mpi_node.sh" "${host}:${PORT}" "$@"
  fi
  # single-host mpirun (dev box): ranks need the same CPU-mesh env the
  # local fallback and local.sh force, or every rank grabs the same
  # default platform/device and the mesh is wrong; `env` rides inside
  # the command so it works for OpenMPI and MPICH alike
  exec "${MPIRUN}" -np "${N}" \
    env \
    JAX_PLATFORMS=cpu \
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${PS_LOCAL_DEVICES:-2}" \
    "${DIR}/mpi_node.sh" "127.0.0.1:${PORT}" "$@"
fi

# ---- no MPI runtime: local emulation through the same adapter ----
echo "mpi_root.sh: ${MPIRUN} not found; emulating ${N} local ranks" >&2
DEVS=${PS_LOCAL_DEVICES:-2}
pids=()
cleanup() { kill "${pids[@]}" 2>/dev/null || true; }
trap cleanup INT TERM
for ((i = N - 1; i >= 0; i--)); do
  env \
    JAX_PLATFORMS=cpu \
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${DEVS}" \
    PS_PROCESS_ID="$i" PS_NUM_PROCESSES="$N" \
    "${DIR}/mpi_node.sh" "127.0.0.1:${PORT}" "$@" &
  pids+=($!)
done
# fail fast, and disambiguate "no children left" from a child that
# itself exited 127 (command not found): wait -n -p reports WHICH pid
# was reaped; 127 with no reaped pid means the set is drained
rc=0
remaining=${#pids[@]}
while (( remaining > 0 )); do
  r=0
  reaped=""
  wait -n -p reaped "${pids[@]}" 2>/dev/null || r=$?
  if [[ -z ${reaped} ]]; then break; fi  # set drained
  remaining=$((remaining - 1))
  if (( r != 0 )); then
    if (( rc == 0 )); then rc=$r; fi   # first failure wins, not SIGTERMs
    cleanup
  fi
done
exit "$rc"
