"""The benchmark's own tests: ``python -m pytest bench/tests``.

They run on the CPU at tiny sizes; the chip is never needed. The modules
of ``bench/`` and the program under ``src/`` are put on the path here.
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
