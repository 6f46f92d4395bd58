#!/usr/bin/env bash
# Host-side tuning for benchmark runs. Source this (don't execute it)
# from a bench entrypoint:
#
#   source scripts/host_tune.sh
#
# tcmalloc via LD_PRELOAD: glibc malloc serializes the allocator under
# XLA's multi-threaded host execution; tcmalloc's per-thread caches remove
# that contention. Preloaded only if an installed copy is found — a bare
# container runs unchanged.
#
# The platform is left to JAX: on a TPU host the benchmarks run on the
# chip. A CPU run with fake devices is asked for explicitly, e.g.
#   JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
#     bash scripts/bench.sh
#
# Everything exported here lands in the bench artifact's "host" block
# (benchmarks/common.py host_env()), so a tuned run is distinguishable
# from a bare one. Explicit env vars always win: each export below keeps
# a value the caller already set.

_repro_find_tcmalloc() {
  local candidates=(
    /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
    /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4
    /usr/lib/libtcmalloc.so.4
    /usr/lib64/libtcmalloc.so.4
  )
  local c
  for c in "${candidates[@]}"; do
    if [[ -e "$c" ]]; then
      echo "$c"
      return 0
    fi
  done
  return 1
}

if [[ -z "${LD_PRELOAD:-}" ]]; then
  if _tcmalloc="$(_repro_find_tcmalloc)"; then
    export LD_PRELOAD="$_tcmalloc"
  fi
  unset _tcmalloc
fi

# marker: bench artifacts record that this file configured the host
export REPRO_HOST_TUNE="tcmalloc=${LD_PRELOAD:-none};${XLA_FLAGS:-}"
