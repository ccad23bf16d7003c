"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They check the yardstick (FLOP counts, the trace reduction, the references,
the manifest) and rehearse run.py at a toy size. No time, rate or
utilization comes out of them.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
