#!/usr/bin/env bash
# Tier-1 test runner with a deterministic multidevice environment.
#
# shard_map tests (collectives, distributed AMG) need several devices; on
# CPU-only machines XLA fakes them with --xla_force_host_platform_device_count,
# which tests/conftest.py sets before any jax import.  Usage:
#
#   bash test.sh                       # whole tier-1 suite
#   bash test.sh tests/test_core_plan.py -k rounds
set -euo pipefail
cd "$(dirname "$0")"

export PYTHONPATH="$PWD/src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# Verify-on-insertion: every plan entering the PlanCache is statically
# checked (repro.verify) in tests/CI; production hot paths leave it unset.
export REPRO_VERIFY="${REPRO_VERIFY:-1}"
# Deterministic hashing: plan/pattern fingerprints are content-hashed
# (blake2b), but set ordering anywhere upstream must not depend on the
# per-process hash seed — pin it so every run and every CI shard agrees
# (tests/test_dense_collectives.py asserts cross-process stability).
export PYTHONHASHSEED="${PYTHONHASHSEED:-0}"

exec /usr/bin/env python3 -m pytest -x -q "$@"
