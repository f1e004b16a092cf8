#!/usr/bin/env bash
# Tier-1 verify — the ROADMAP.md command, verbatim, so CI and humans run the
# exact same gate. Exits with pytest's status; prints DOTS_PASSED for the
# no-worse-than-seed comparison.
cd "$(dirname "$0")/.." || exit 1

# Static-analysis gate (BLOCKING): lock discipline (guarded_by proofs +
# lock-order cycles), determinism/parity rules, and the AOT-contract diff
# against docs/aot_contract.json — tools/lskcheck.py, rule catalog in
# docs/ANALYSIS.md. Any unwaived finding or contract drift fails the
# build; the machine-readable report lands in ANALYSIS.json (CI artifact).
timeout -k 10 300 python tools/lskcheck.py --json ANALYSIS.json
lskrc=$?

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

# the lskcheck gate blocks even when the tests pass (and never masks a
# test failure — the first nonzero status wins)
[ "$rc" -eq 0 ] && rc=$lskrc
exit $rc
