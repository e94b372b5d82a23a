"""The configuration, the two cells, the traffic mix, the plain model and
the four per-layer metrics ISSUE 41 added to the benchmark run with the
tier-1 suite: the cases live beside the benchmark's other tests."""

from benchmark.tests.test_check_repair_cells import *  # noqa: F401,F403
