"""The harness's rules for its metrics, its draw of faults and its seeds
run with the tier-1 suite: the cases live beside the benchmark's other
tests."""

from benchmark.tests.test_metric_rules import *  # noqa: F401,F403
