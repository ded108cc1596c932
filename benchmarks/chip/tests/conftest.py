"""The benchmark's CPU tests import the harness from the checkout's root
and the program from ``src``, as ``run.py`` does:

    python3 -m pytest benchmarks/chip/tests
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[3]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)
