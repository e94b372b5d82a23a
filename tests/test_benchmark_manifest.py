"""`BENCHMARK.json` against the files it names runs with the tier-1
suite: the cases live beside the benchmark's other tests."""

from benchmark.tests.test_benchmark_json import *  # noqa: F401,F403
