"""The benchmark's tests import its modules by their own names
(``harness``, ``traffic``, ...) from ``bench/``."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
