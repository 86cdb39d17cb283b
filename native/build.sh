#!/bin/sh
# Build the native host runtime into native/build/libconsensus_native.so.
# One recipe: the Python loader (hashgraph_tpu/native.py) compiles and
# stamps the artifact (host ISA + source digest) whenever it is missing
# or stale; this script just asks it to.
set -e
cd "$(dirname "$0")/.."
python3 -c "import sys; from hashgraph_tpu import native; sys.exit(0 if native.available() else 1)"
echo "built native/build/libconsensus_native.so"
