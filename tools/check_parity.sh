#!/usr/bin/env bash
# API-parity gate (reference: tools/print_signatures.py + paddle/fluid/API.spec
# CI gate — the reference diffs live signatures against a checked-in spec;
# here the spec IS the reference tree's own __all__ lists, and the gate tests
# compare this package against them name by name).
#
# Usage: tools/check_parity.sh [extra pytest args]
# Runs every parity-gate test on the 8-virtual-device CPU mesh.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

exec python -m pytest -q \
  tests/test_api_tail.py \
  tests/test_namespace_tail.py \
  tests/test_legacy_tail.py \
  tests/test_nn_tail.py \
  tests/test_static_nn.py::test_static_nn_parity_gate \
  tests/test_api_fingerprint.py \
  "$@"
