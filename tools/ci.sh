#!/bin/bash
# One-command CI: the static gates, the whole test suite, and the entry
# points that are not tests (the 8-device dry run, chip_smoke.py's
# rehearsal, the examples). Everything runs on the host CPU
# (JAX_PLATFORMS=cpu is exported below), so it says whether the code is
# correct, never how fast the chip is: speed is benchmark/run.py on the
# chip (benchmark/README.md), and the chip's bring-up check is
# `python chip_smoke.py` through the chip tool.
#
#   bash tools/ci.sh            # full run
#   CI_FAST=1 bash tools/ci.sh  # skip the full pytest suite
#
# Exit code 0 = every stage green. Log: ${TMPDIR:-/tmp}/flinkml_ci_<UTC>.log
# (never inside the checkout).
set -u
cd "$(dirname "$0")/.."
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS=cpu
MESH8="--xla_force_host_platform_device_count=8"
STAMP=$(date -u +%Y%m%dT%H%M%SZ)
LOG="${TMPDIR:-/tmp}/flinkml_ci_${STAMP}.log"
exec > >(tee "$LOG") 2>&1

FAIL=0
stage() {  # stage <name> <cmd...>
    local name=$1; shift
    echo "=== ci: $name ==="
    local t0=$SECONDS
    if "$@"; then
        echo "=== ci: $name OK ($((SECONDS - t0))s) ==="
    else
        echo "=== ci: $name FAILED rc=$? ($((SECONDS - t0))s) ==="
        FAIL=1
    fi
}

stage "lint (compileall)" python -m compileall -q \
    flinkml_tpu benchmark tests tools examples chip_smoke.py __graft_entry__.py

# Ahead-of-time analysis gate (docs/development/static_analysis.md):
# examples must lint clean (all passes, device-free), and the seeded
# fixtures must FAIL — proving the gate has teeth.
stage "analysis gate (examples clean)" \
    python -m flinkml_tpu.analysis examples/ --fail-on-findings
analysis_fixture_gate() {
    if python -m flinkml_tpu.analysis \
        tests/analysis_fixtures/ --no-selfcheck --fail-on-findings; then
        echo "analysis gate passed the seeded-findings fixtures (it must flag them)"
        return 1
    fi
}
stage "analysis gate (fixtures flagged)" analysis_fixture_gate

# All of tests/, the `slow` ones too (tier-1 runs `-m 'not slow'`): the
# process-spawning cases of test_cluster.py, test_distributed.py and
# test_recovery.py run here and nowhere else.
if [ "${CI_FAST:-0}" != 1 ]; then
    stage "full suite" python -m pytest tests/ -x -q
fi

dryrun_8_devices() {
    XLA_FLAGS="$MESH8" python -c \
        "import __graft_entry__ as g; g.entry(); g.dryrun_multichip(8)"
}
stage "8-device dryrun" dryrun_8_devices

# chip_smoke.py's rehearsal: every phase of the chip check at tiny sizes
# on the 8-device CPU mesh (Pallas interpreted). tests/test_chip_smoke.py
# reads its lines; here it only has to pass, and the default invocation
# has to refuse this backend.
chip_smoke_rehearsal() {
    local out
    out=$(XLA_FLAGS="$MESH8" timeout 600 python chip_smoke.py --rehearse) \
        || return 1
    printf '%s\n' "$out" | tail -1
    if python chip_smoke.py >/dev/null 2>&1; then
        echo "chip_smoke.py ran on a non-TPU backend (it must refuse)"
        return 1
    fi
}
stage "chip_smoke rehearsal (CPU mesh)" chip_smoke_rehearsal

example_smoke() {
    local ex
    for ex in serve_pipeline parallel_primitives checkpoint_resume \
              sparse_high_cardinality; do
        echo "--- example: $ex ---"
        XLA_FLAGS="$MESH8" timeout 420 python "examples/${ex}.py" || return 1
    done
}
stage "example smoke (CPU mesh)" example_smoke

if [ "$FAIL" = 0 ]; then
    echo "=== ci: ALL STAGES GREEN (log: $LOG) ==="
else
    echo "=== ci: FAILURES — see $LOG ==="
fi
exit $FAIL
