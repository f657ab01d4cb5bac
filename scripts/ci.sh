#!/usr/bin/env bash
# CI tiers: install dev deps (best effort — the offline container already
# bakes in jax/pytest), then run the requested tier on CPU. The Pallas
# kernels run in interpret mode inside the tests — training fwd+bwd
# (tests/test_differential.py, tests/test_kernels_block_sparse.py) and the
# fused chunk/decode serving kernel (tests/test_chunk_kernel.py, DESIGN.md
# §11), whose in-kernel top-m selection differential subset runs in BOTH
# tile modes (latency single-query + throughput multi-query MXU tiles,
# test_kernel_forced_modes_match_jnp / test_kernel_oversubscribed_budget)
# and stays interpret-mode-bounded (small nb, C <= 5) so the fast tier's
# wall time holds — so both TPU paths are exercised end-to-end on every
# CPU run. The
# fast tier also pins the cross-family serving contract: registry signature
# conformance (tests/test_registry_contract.py) and the recurrent/hybrid
# engine's batched == solo guarantees (tests/test_recurrent_engine.py,
# DESIGN.md §12). The shard tier re-runs the training/serving stack, serving
# kernel included, under 8 fake host devices (tests/test_shard_parity.py,
# plus the recurrent-engine DP x TP parity in tests/test_recurrent_engine.py).
#
# Usage:
#   scripts/ci.sh          # fast tier (default: pytest -m "not slow and not shard")
#   scripts/ci.sh lint     # ruff check + format check (skipped if ruff missing)
#   scripts/ci.sh shard    # sharded-vs-single-device parity on 8 fake devices
#   scripts/ci.sh slow     # the slow tier only
#   scripts/ci.sh all      # everything
set -euo pipefail
cd "$(dirname "$0")/.."

if ! python -c "import hypothesis" >/dev/null 2>&1; then
  # offline containers skip this cleanly; hypothesis-only tests importorskip
  pip install --retries 0 --timeout 5 -r requirements-dev.txt \
    || echo "[ci] dev-dep install skipped (offline?)"
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

case "${1:-fast}" in
  fast)
    python -m pytest -x -q                       # pytest.ini deselects slow+shard
    # telemetry smoke (DESIGN.md §13): metrics snapshot round-trips through
    # JSON, reservoirs stay bounded, trace events validate as Chrome-trace
    # (imported form avoids runpy's found-in-sys.modules warning)
    python -c "from repro.serve import telemetry; telemetry._selftest()"
    # speculative-decoding smoke (DESIGN.md §10): K=2, tiny model, jnp paths
    # (kernels stay in interpret-capable territory on the decode side)
    python -m benchmarks.spec_bench --smoke
    # H-level long-context smoke (DESIGN.md §14): an H=3 engine streams a
    # context 8x its fine window through the interpret-mode serving kernel,
    # collapsing evicted pages up the hierarchy (asserts per-level occupancy
    # + bounded live window internally)
    python -m benchmarks.serve_bench --long-ctx-smoke --interpret
    ;;
  lint)
    # tracked bytecode is a repo-hygiene regression (76 .pyc files were once
    # committed by accident); fail fast if it ever reappears
    if git -C . rev-parse --git-dir >/dev/null 2>&1; then
      TRACKED_PYC=$(git ls-files -- '*.pyc' '**/__pycache__/**' | head -5)
      if [ -n "$TRACKED_PYC" ]; then
        echo "[ci] FAIL: compiled bytecode is tracked by git:" >&2
        echo "$TRACKED_PYC" >&2
        exit 1
      fi
    fi
    if python -m ruff --version >/dev/null 2>&1; then RUFF="python -m ruff";
    elif command -v ruff >/dev/null 2>&1; then RUFF="ruff";
    else
      echo "[ci] ruff not installed; lint tier skipped (offline container)"
      exit 0
    fi
    $RUFF check .
    # Format drift is reported, not gating, until the tree has been formatted
    # once with a pinned ruff (the repo predates the formatter; blind-gating
    # would red the job on style the linter can auto-fix with `ruff format`).
    $RUFF format --diff . || echo "[ci] ruff format drift (non-gating; run 'ruff format .')"
    ;;
  shard)
    # The parity tests spawn their own subprocesses with the device-count
    # flag; exporting it here also covers any future in-process mesh tests.
    export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"
    python -m pytest -x -q -m shard
    ;;
  slow) python -m pytest -x -q -m slow ;;
  all)  python -m pytest -x -q -m "" ;;
  *)    echo "usage: scripts/ci.sh [fast|lint|shard|slow|all]" >&2; exit 2 ;;
esac
