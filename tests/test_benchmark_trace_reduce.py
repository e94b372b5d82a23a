"""The reduction from a device trace to busy time, idle gaps and named
operations runs with the tier-1 suite: the cases live beside the
benchmark's other tests."""

from benchmark.tests.test_trace_reduce import *  # noqa: F401,F403
