"""The harness end to end on the CPU (`run.py --rehearse`) runs with the
tier-1 suite: the cases live beside the benchmark's other tests.

The one of them that counts and runs the crossings of configuration and
traffic mix is held in `tests/test_benchmark_crossings.py`, in the form
that stays true under appending and each crossing a case of its own: a
file of its own since PR 43, so that the two halves run on two workers
(this file alone took 844 s of one before: driver, PR 42)."""

from benchmark.tests.test_rehearsal import *  # noqa: F401,F403

del test_the_crossings_run_from_data_alone  # noqa: F821
