#!/usr/bin/env bash
# Test runner (reference parity: run_test.sh).  Runs the full suite — the
# store integration tests parametrize over both server backends (python
# asyncio + native C++ epoll) and both client implementations.
set -euo pipefail
cd "$(dirname "$0")"

# Build the native runtime up front so its absence is loud, not silently
# skipped by the graceful-fallback path.
make -C src

# JAX surfaces run on a virtual 8-device CPU mesh (conftest pins the
# platform unless ISTPU_TEST_TPU is set on a machine with a chip).
exec python -m pytest tests/ -q "$@"
