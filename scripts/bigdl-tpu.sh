#!/usr/bin/env bash
# Launcher: set the XLA/JAX environment the way the reference's
# scripts/bigdl.sh:40-47 sets the MKL/OMP environment, then exec the wrapped
# command. Usage:
#   ./scripts/bigdl-tpu.sh -- python -m bigdl_tpu.apps.lenet train -b 256
#   ./scripts/bigdl-tpu.sh -- bigdl-tpu-perf --model resnet50
#   ./scripts/bigdl-tpu.sh lint [paths... --select/--ignore/--format ...]
#   ./scripts/bigdl-tpu.sh metrics [url|--selftest]   # scrape /metrics
#   ./scripts/bigdl-tpu.sh trace [file|--selftest]    # Chrome trace tools
#   ./scripts/bigdl-tpu.sh scoreboard [...|diff a b]  # serving scoreboard
#   ./scripts/bigdl-tpu.sh chaos {corrupt|selftest|drill} ...  # fault injection
#   ./scripts/bigdl-tpu.sh resilience {validate|latest} <ckpt_dir>
#   ./scripts/bigdl-tpu.sh serve [--replicas N] [--disaggregate P:D] ...
set -euo pipefail

# --- lint subcommand: graftlint, the whole-program JAX-hazard analyzer
#     (docs/ANALYSIS.md). With no path arguments the CLI itself defaults
#     to the tier-1 self-lint gate tree (bigdl_tpu/ + scripts/, resolved
#     from the package location), so flags-only invocations like
#     `lint --format json` cover the same tree. Fast local gating and CI
#     annotation:
#       ./scripts/bigdl-tpu.sh lint --changed HEAD     # changed files only
#       ./scripts/bigdl-tpu.sh lint --sarif out.sarif  # SARIF 2.1.0 report
if [[ "${1:-}" == "lint" ]]; then
  shift
  root="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
  export PYTHONPATH="$root${PYTHONPATH:+:$PYTHONPATH}"
  exec python -m bigdl_tpu.analysis "$@"
fi

# --- telemetry subcommands (docs/OBSERVABILITY.md): scrape a serving
#     process's /metrics, validate/produce Chrome trace dumps, or run the
#     serving scoreboard (workload driver + regression diff).
#       ./scripts/bigdl-tpu.sh metrics localhost:8000
#       ./scripts/bigdl-tpu.sh trace /tmp/bigdl_trace.json
#       ./scripts/bigdl-tpu.sh scoreboard --out sb.json --markdown
#       ./scripts/bigdl-tpu.sh scoreboard diff old.json new.json
if [[ "${1:-}" == "metrics" || "${1:-}" == "trace" \
      || "${1:-}" == "scoreboard" ]]; then
  sub="$1"; shift
  root="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
  export PYTHONPATH="$root${PYTHONPATH:+:$PYTHONPATH}"
  exec python -m bigdl_tpu.telemetry "$sub" "$@"
fi

# --- resilience subcommands (docs/RESILIENCE.md): snapshot audits and
#     deterministic fault injection against checkpoint directories, plus
#     the serving-plane kill-one-replica drill.
#       ./scripts/bigdl-tpu.sh chaos corrupt /ckpt/model.40 --mode flip
#       ./scripts/bigdl-tpu.sh chaos drill --disaggregate 1:2
#       ./scripts/bigdl-tpu.sh resilience validate /ckpt
if [[ "${1:-}" == "chaos" || "${1:-}" == "resilience" ]]; then
  sub="$1"; shift
  root="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
  export PYTHONPATH="$root${PYTHONPATH:+:$PYTHONPATH}"
  if [[ "$sub" == "chaos" ]]; then
    exec python -m bigdl_tpu.resilience chaos "$@"
  fi
  exec python -m bigdl_tpu.resilience "$@"
fi

# --- serving fleet (docs/RESILIENCE.md): stdlib HTTP front over N
#     in-process replicas with graceful SIGTERM drain; --disaggregate
#     P:D splits prefill from decode replicas.
#       ./scripts/bigdl-tpu.sh serve --replicas 2 --port 8000
if [[ "${1:-}" == "serve" ]]; then
  shift
  root="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
  export PYTHONPATH="$root${PYTHONPATH:+:$PYTHONPATH}"
  exec python -m bigdl_tpu.apps.transformer serve "$@"
fi

# --- compilation cache: nothing to do here. JAX_COMPILATION_CACHE_DIR, when
#     exported, is read by jax itself; otherwise `import bigdl_tpu` points the
#     cache at <checkout>/.jax_cache (utils/engine.py compile_cache_dir).

# --- host-side threading: BLAS/OpenMP on the host should not fight the
# data-pipeline IO pool (reference pins OMP_NUM_THREADS=1, KMP_BLOCKTIME=0)
export OMP_NUM_THREADS="${OMP_NUM_THREADS:-1}"
export OPENBLAS_NUM_THREADS="${OPENBLAS_NUM_THREADS:-1}"

# --- TPU runtime knobs (harmless on CPU): async collectives on by default
export LIBTPU_INIT_ARGS="${LIBTPU_INIT_ARGS:-}"

# --- multi-host: forward a coordinator if the scheduler provided one
#     (BIGDL_COORDINATOR_ADDRESS / BIGDL_NUM_PROCESSES / BIGDL_PROCESS_ID
#     are read by bigdl_tpu.utils.engine.Engine.init)

# --- optional CPU simulation: BIGDL_TPU_SIMULATE=N fakes an N-chip mesh
if [[ -n "${BIGDL_TPU_SIMULATE:-}" ]]; then
  export JAX_PLATFORMS=cpu
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${BIGDL_TPU_SIMULATE}"
fi

if [[ "${1:-}" == "--" ]]; then shift; fi
if [[ $# -eq 0 ]]; then
  echo "usage: $0 -- <command ...>" >&2
  exit 2
fi
exec "$@"
