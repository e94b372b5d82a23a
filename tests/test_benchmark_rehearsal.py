"""The harness end to end on the CPU (`run.py --rehearse`) runs with the
tier-1 suite: the cases live beside the benchmark's other tests."""

from benchmark.tests.test_rehearsal import *  # noqa: F401,F403
