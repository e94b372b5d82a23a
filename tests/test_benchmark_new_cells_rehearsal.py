"""The rehearsal of the cells ISSUE 29 added runs with the tier-1 suite:
the cases live beside the benchmark's other tests."""

from benchmark.tests.test_new_cells_rehearsal import *  # noqa: F401,F403
