"""The two readers and the eleven per-layer metrics ISSUE 38 added to the
benchmark run with the tier-1 suite: the cases live beside the
benchmark's other tests."""

from benchmark.tests.test_wire_ends import *  # noqa: F401,F403
