"""Tests of the benchmark run on the CPU at toy size: the harness
internals, the reference, the controls and the trace reduction.  The
command line itself refuses a CPU, which one test checks."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
