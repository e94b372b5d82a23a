"""The harness's chained path (ISSUE 28: previous signatures in the
stand, the reference, the planted faults and the rehearsal's verifier)
runs with the tier-1 suite: the cases live beside the benchmark's other
tests."""

from benchmark.tests.test_chained import *  # noqa: F401,F403
