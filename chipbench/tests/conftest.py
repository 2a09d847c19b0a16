"""chipbench's own tests: run by hand (`JAX_PLATFORMS=cpu python -m pytest
chipbench/tests -q -p no:cacheprovider`), outside tier-1's `testpaths`."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
