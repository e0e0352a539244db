#!/usr/bin/env bash
# Full local verification gate: tier-1 tests, then the acs-lint static
# gate, stopping at the first failure.  `make verify` runs this; CI and
# pre-commit should too.
#
# tests/conftest.py puts the tests on the CPU, so no step takes a chip.
# Tier-1 also compiles the main device programs for a described TPU v5e
# and scans each for 64-bit types (tests/test_tpu_compile.py).
set -o pipefail
cd "$(dirname "$0")"

echo "== [1/2] tier-1 tests (pytest -m 'not slow') =="
rm -f /tmp/_t1.log
timeout -k 10 870 python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
if [ "$rc" -ne 0 ]; then
    echo "verify: FAILED at tier-1 tests (rc=$rc)" >&2
    exit "$rc"
fi

echo "== [2/2] acs-lint (zero new findings vs baseline) =="
if ! python -m access_control_srv_tpu.analysis; then
    echo "verify: FAILED at acs-lint" >&2
    exit 1
fi

echo "verify: OK"
