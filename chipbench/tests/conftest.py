"""CPU tests of the benchmark's own code: the comparison and its faults,
the trace reduction, and the kernel's byte count. Run with
``python -m pytest chipbench/tests`` from the repository root."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
