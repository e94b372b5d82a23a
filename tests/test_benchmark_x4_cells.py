"""The configuration, the two cells and the two per-layer metrics ISSUE 34
added to the benchmark run with the tier-1 suite: the cases live beside
the benchmark's other tests."""

from benchmark.tests.test_x4_cells import *  # noqa: F401,F403
