"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests

They are not part of the repository's tier-1 suite (``testpaths``)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
