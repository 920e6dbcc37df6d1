"""On-chip benchmark of the GPU First runtime and its serving path.

Run one cell with ``python3 -m bench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.  ``BENCHMARK.json``
names the cells; each cell's configuration, traffic mix and per-layer metric
readers are files of their own under this directory, found by name.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

# the system under test lives in src/ of the same checkout
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
