"""The configuration, the two cells and the four per-layer metrics ISSUE 36
added to the benchmark run with the tier-1 suite: the cases live beside
the benchmark's other tests."""

from benchmark.tests.test_2chains_cells import *  # noqa: F401,F403
