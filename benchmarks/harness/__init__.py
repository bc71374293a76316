"""The benchmark's yardstick: how a cell is found, run, traced and reduced
to the one result line.  See ``PERF.md`` and ``BENCHMARK.json``."""
