"""The benchmark's readers of program spans and device scopes run with
the tier-1 suite: the cases live beside the benchmark's other tests."""

from benchmark.tests.test_readers import *  # noqa: F401,F403
