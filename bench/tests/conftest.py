"""The benchmark's self-tests run on the CPU at tiny sizes:

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are not part of the repository's tier-1 suite (``testpaths`` is
``tests/``)."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: tiny sizes of the population cells, for runs without a chip
POP_TINY = {"users": 6000, "warm_buckets": [16], "sample": 384}
