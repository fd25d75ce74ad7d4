#!/bin/bash
# Nightly — role parity with reference ci/nightly-build.sh: clean rebuild,
# self-test, full suite. Speed is measured by benchmark/run.py, on a chip.
set -euo pipefail
cd "$(dirname "$0")/.."

rm -rf build/native
cmake -S src/native -B build/native -G Ninja
ninja -C build/native
./build/native/tpudf_selftest
python build_scripts/build-info.py
python -m pytest tests/ -q
