"""Make the benchmark's modules and the program importable in its tests."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
