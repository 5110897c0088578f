"""The benchmark's self-tests, run by hand on the CPU (and, for
``test_control.py``, on the chip):

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
