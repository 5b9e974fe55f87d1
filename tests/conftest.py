import os
import sys
from pathlib import Path

# benchmarks package (repo root) importable from tests
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# Tests must see ONE device (the dry-run owns the 512-device flag).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
