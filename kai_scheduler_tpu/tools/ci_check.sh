#!/usr/bin/env bash
# ci_check.sh — one command reproduces the full static + test gate
# locally, exactly as CI runs it:
#
#   ruff          style/pyflakes subset (config: pyproject.toml; the
#                 step is skipped with a warning when ruff is not
#                 installed — the hermetic test image does not bake it)
#   kailint       the project-specific invariant rules KAI001-KAI008
#                 (docs/STATIC_ANALYSIS.md) against the committed
#                 baseline (.kailint-baseline.json)
#   kairace       the whole-program thread-role & lock-contract rules
#                 KRC001-KRC005 (docs/STATIC_ANALYSIS.md) — the
#                 committed baseline (.kairace-baseline.json) is EMPTY
#                 by contract, so any finding is a new race to fix
#   kaijit        the whole-program JAX compilation-contract rules
#                 KJT001-KJT006 (docs/STATIC_ANALYSIS.md) — unbucketed
#                 shapes feeding jit, retrace-prone static args, traced
#                 host escapes, dtype-pin violations, mutable closure
#                 captures, donation contract; the committed baseline
#                 (.kaijit-baseline.json) is EMPTY by contract
#   chaos matrix  --dry-run validation of the fault-grid definition
#                 (including the --races KAI_LOCKTRACE lock-order
#                 validation mode, the --wire-faults lying-wire ring,
#                 the --compile KAI_JITTRACE compile-contract ring,
#                 and the --wiretrace distributed-trace/byte-account
#                 chaos ring)
#   conformance   tools/conformance.py --smoke: every proof in one
#                 command — all three analyzers, every chaos-matrix
#                 mode definition, and a real 1-seed wire-faults sweep
#   kernel parity the grouped fill's two rungs (Pallas, jnp) vs the
#                 exact kernel: placements must be bit-identical
#                 (tools/kernel_parity.py --smoke)
#   stackprof     continuous-profiler smoke: profile a short embedded
#                 fleet burst, fail on an empty folded profile
#   fleet budget  bench.py fleet phase at a small shape vs the committed
#                 threshold file (docs/scale-tests/fleet_budget.json):
#                 grouped/snapshotted phase medians, warm cycle, the
#                 incremental-cache structural gates, the fused-allocate
#                 kernel ceiling, the 10k-queue fair-share step
#                 ceiling + single-dispatch/prep-reuse structural gates,
#                 the overlapped-pipeline re-run (identical bound
#                 pods, overlap-ratio floor), the columnar
#                 host-state gates (zero fallbacks warm, columnar rows
#                 served, snapshot-build ceiling), and the http
#                 daemon-regime gates (zero steady-state whole-kind
#                 lists, bulk-endpoint hit floors, preserialized
#                 frame-cache hit ratio) must stay in budget — the
#                 whole run traces under KAI_JITTRACE, so the committed
#                 per-kernel compile-signature ceilings
#                 (docs/scale-tests/compile_budget.json) gate here too,
#                 as do the wire-observatory per-cycle ceilings
#                 (docs/scale-tests/wire_budget.json): bytes/syscalls/
#                 encodes per cycle, serve-path re-encode cap, the
#                 frame-cache byte-hit floor, and a grafted-span floor
#   tier-1 tests  pytest -m 'not slow' on CPU
#
# Usage: kai_scheduler_tpu/tools/ci_check.sh [--no-tests]
set -u
ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/../.." && pwd)"
cd "$ROOT"
fail=0

echo "== ruff =="
if command -v ruff >/dev/null 2>&1; then
    ruff check kai_scheduler_tpu/ tests/ bench.py || fail=1
else
    echo "skipped: ruff not installed (pip install ruff; config already"
    echo "in pyproject.toml [tool.ruff])"
fi

echo
echo "== kailint =="
python -m kai_scheduler_tpu.tools.kailint kai_scheduler_tpu/ || fail=1

echo
echo "== kairace (thread-role & lock-contract analyzer) =="
python -m kai_scheduler_tpu.tools.kairace kai_scheduler_tpu/ || fail=1

echo
echo "== kaijit (JAX compilation-contract analyzer) =="
python -m kai_scheduler_tpu.tools.kaijit kai_scheduler_tpu/ || fail=1

echo
echo "== chaos matrix definition (dry run) =="
python -m kai_scheduler_tpu.tools.chaos_matrix --dry-run || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --pipeline --dry-run \
    || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --columnar --dry-run \
    || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --wire --dry-run \
    || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --timeaware --dry-run \
    || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --wire-faults --dry-run \
    || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --races --dry-run \
    || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --compile --dry-run \
    || fail=1
python -m kai_scheduler_tpu.tools.chaos_matrix --wiretrace --dry-run \
    || fail=1

echo
echo "== conformance ring (--smoke: analyzers + matrix defs + 1-seed"
echo "   wire-faults sweep in one command — tools/conformance.py) =="
JAX_PLATFORMS=cpu python -m kai_scheduler_tpu.tools.conformance --smoke \
    || fail=1

echo
echo "== kernel-parity smoke (jnp and pallas rungs vs exact) =="
JAX_PLATFORMS=cpu python -m kai_scheduler_tpu.tools.kernel_parity \
    --smoke || fail=1

echo
echo "== stackprof smoke (profile a short fleet burst) =="
JAX_PLATFORMS=cpu python -m kai_scheduler_tpu.utils.stackprof --smoke \
    || fail=1

echo
echo "== fleet-phase budget (host-pipeline medians vs committed budget) =="
JAX_PLATFORMS=cpu python -m kai_scheduler_tpu.tools.fleet_budget \
    || fail=1

if [ "${1:-}" != "--no-tests" ]; then
    echo
    echo "== tier-1 tests (pytest -m 'not slow') =="
    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
        -p no:cacheprovider || fail=1
fi

echo
if [ "$fail" -eq 0 ]; then
    echo "ci_check: ALL GREEN"
else
    echo "ci_check: FAILED (see sections above)"
fi
exit "$fail"
