"""Run by hand and in rehearsal (``python -m pytest benchmarks/tests``);
not part of tier-1, which collects ``tests/`` only."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(BENCH, "lib"))
