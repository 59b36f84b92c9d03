"""Tests of the benchmark's own files (`benchmark/`): tier-1, on the CPU,
no timing asserts, no process spawned, nothing touched at import."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
