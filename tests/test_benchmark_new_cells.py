"""The cells, the configuration and the per-layer metrics ISSUE 29 added
to the benchmark, checked without a run, with the tier-1 suite: the cases
live beside the benchmark's other tests."""

from benchmark.tests.test_new_cells import *  # noqa: F401,F403
