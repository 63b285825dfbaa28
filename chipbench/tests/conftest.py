"""The benchmark's own tests run on the CPU: ``pytest chipbench/tests``.
Sizes are the tiny presets under ``tests/tiny``; the command line of
the benchmark has no option that selects them."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
