#!/usr/bin/env bash
# Static gates: tpulint (JAX/TPU tracing/sharding/thread-safety analyzer,
# tools/tpulint/) project-wide in --strict mode (every suppression must
# carry a reason) against the committed findings baseline — the gate
# fails ONLY on NEW findings, so pre-existing accepted ones never block
# an unrelated change.  Refresh the baseline with
#   python -m tools.tpulint incubator_mxnet_tpu tools ci --strict --write-baseline
# Plus a bytecode compile of package + tools as a syntax gate, and
# hlolint (tools/hlolint/): compiled-program contracts over the HLO of
# the flagship programs, gated against .hlolint_contracts.json — refresh
# with   JAX_PLATFORMS=cpu python ci/hlolint_gate.py --write-contracts
# See docs/static_analysis.md.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "tpulint: analyzing incubator_mxnet_tpu/ tools/ ci/ (baseline gate)"
python -m tools.tpulint incubator_mxnet_tpu tools ci \
    --strict --baseline .tpulint_baseline.json --stats

echo "tpulint: rule modules must ship covering fixtures"
for mod in tools/tpulint/*_rules.py; do
    for code in $(grep -o 'TPU[0-9]\{3\}' "$mod" | sort -u); do
        fix="tests/fixtures/tpulint/$(echo "$code" | tr '[:upper:]' '[:lower:]')_case.py"
        if [[ ! -f "$fix" ]]; then
            echo "FAIL: $mod implements $code but $fix is missing" >&2
            exit 1
        fi
        if ! grep -q "$(basename "$fix")" tests/test_tpulint.py; then
            echo "FAIL: $fix exists but tests/test_tpulint.py never loads it" >&2
            exit 1
        fi
    done
done

echo "tpulint: lock-order graph dump (--format dot)"
lock_dot=$(python -m tools.tpulint incubator_mxnet_tpu --format dot)
grep -q '^digraph lock_order' <<<"$lock_dot"
echo "$lock_dot"

echo "compileall: incubator_mxnet_tpu/ tools/ tests/ ci/"
python -m compileall -q incubator_mxnet_tpu/ tools/ tests/ ci/

echo "hlolint: compiled-program contracts (.hlolint_contracts.json)"
JAX_PLATFORMS=cpu python ci/hlolint_gate.py

echo "telemetry smoke: 3-step train with MXTPU_TELEMETRY_DUMP=1"
JAX_PLATFORMS=cpu python ci/telemetry_smoke.py

echo "input pipeline smoke: sync-vs-prefetched equivalence + metrics"
JAX_PLATFORMS=cpu python ci/input_pipeline_smoke.py

echo "overlap smoke: bucketed-vs-monolithic ZeRO parity + overlap_fraction"
JAX_PLATFORMS=cpu python ci/overlap_smoke.py

echo "quantized decode smoke: int8 weight streaming + greedy parity"
JAX_PLATFORMS=cpu python ci/quantized_decode_smoke.py

echo "flight recorder smoke: SIGTERM mid-train ships a parseable bundle"
JAX_PLATFORMS=cpu python ci/flight_recorder_smoke.py

echo "resume smoke: kill-and-resume on a halved mesh, async stall < 10% sync"
JAX_PLATFORMS=cpu python ci/resume_smoke.py

echo "serving smoke: overloaded Poisson run — sheds, drains, 0 recompiles"
JAX_PLATFORMS=cpu python ci/serving_smoke.py

echo "lint gates: OK"
