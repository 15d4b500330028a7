"""Puts the checkout root on ``sys.path`` so ``benchmarks.chip`` imports."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
