import os
import sys

# run by hand: python -m pytest bench/tests (outside the repo's testpaths)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")]
