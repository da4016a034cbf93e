"""The benchmark's own tests: ``pytest bench/tests``. They run on the CPU
(``JAX_PLATFORMS=cpu``) at small sizes."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src"), str(HERE)) if p not in sys.path]
