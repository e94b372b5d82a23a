"""The configuration, the cell, the traffic mix, the plain model of a
fail-over and the four per-layer metrics ISSUE 43 added to the benchmark
run with the tier-1 suite: the cases live beside the benchmark's other
tests."""

from benchmark.tests.test_failover_cells import *  # noqa: F401,F403
