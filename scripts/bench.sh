#!/usr/bin/env bash
# Benchmark runner with host tuning applied. Single source of truth for
# the bench environment: .github/workflows/ci.yml calls this script, so
# running it locally reproduces the CI bench job exactly.
#
#   bash scripts/bench.sh                         # the CI artifact set
#   bash scripts/bench.sh benchmarks.bench_serve  # one module
#
# Host flags (tcmalloc LD_PRELOAD when available) come from
# scripts/host_tune.sh and are recorded into every BENCH_*.json under
# "host". The platform is JAX's default: the chip where there is one.
# Modules run one after another from this shell, which never imports JAX,
# so each process has the chip to itself.
set -euo pipefail
cd "$(dirname "${BASH_SOURCE[0]}")/.."

source scripts/host_tune.sh
export PYTHONPATH="src${PYTHONPATH:+:${PYTHONPATH}}"

if [[ $# -gt 0 ]]; then
  for mod in "$@"; do
    python -m "$mod"
  done
  exit 0
fi

python -m benchmarks.elastic_switch
python -m benchmarks.bench_hotpath
python -m benchmarks.bench_stream
python -m benchmarks.bench_serve
python -m benchmarks.bench_profile
python -m benchmarks.bench_faults
python -m benchmarks.fig6_scaling
