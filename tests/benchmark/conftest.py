"""The benchmark lives at the root of the checkout, beside ``tests/``: make it
importable however pytest was started."""

import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
