"""Tests of the benchmark harness, run by hand (outside the repository's
tier-1 ``testpaths``):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run on the CPU at a tiny size; nothing here needs a chip."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
