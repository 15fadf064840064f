#!/usr/bin/env bash
# The one lint gate CI (and a pre-commit human) runs: domain rules —
# per-file TDA0xx AND the project-graph TDA1xx interprocedural pass —
# style (ruff, when installed — `tda lint` chains it over the same
# files), and the wire-contract document. Any failure fails the gate;
# each tool prints its own findings.
#
#   scripts/lint_gate.sh            # gate the default surface
#   scripts/lint_gate.sh --fix      # apply the mechanically-safe fixes
#                                   # first (TDA021 daemon=, suppression
#                                   # scaffolds/removals), then gate
set -u
cd "$(dirname "$0")/.."

rc=0

# 1. domain lint: per-file rules + the whole-program project graph
#    (chains ruff itself when installed)
python -m tpu_distalg.cli lint tpu_distalg/ tests/ scripts/ \
    --baseline lint_baseline.json "$@" || rc=1

# 2. the same engine through --format json: a smoke test that the
#    project-graph pass not only finds nothing but RUNS — an engine
#    crash (unparseable summary, resolver recursion, cache decode)
#    must fail the gate even on a findings-clean tree
python -m tpu_distalg.cli lint tpu_distalg/ tests/ scripts/ \
    --baseline lint_baseline.json --format json --no-ruff \
    > /dev/null || rc=1

# 3. the wire contract: docs/PROTOCOL.md must match what the
#    protocol-graph extractor recovers from source (the document
#    can never drift)
python -m tpu_distalg.cli protocol --check || rc=1

# 4. the protocol extractor through --format json: engine-crash smoke
#    on the machine-readable path, per the step-2 convention
python -m tpu_distalg.cli protocol --format json > /dev/null || rc=1

if [ "$rc" -ne 0 ]; then
    echo "lint gate: FAILED" >&2
else
    echo "lint gate: OK"
fi
exit "$rc"
