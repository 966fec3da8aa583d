"""Where the chip benchmark and its test fixture are."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark", "chip")
FIXTURE = os.path.join(HERE, "fixture")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
