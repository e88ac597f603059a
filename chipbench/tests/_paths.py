"""Puts the benchmark and the program on ``sys.path`` for the tests here."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
