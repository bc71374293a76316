"""The benchmark's command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips; no child touches jax.  It
refuses to run unless ``jax.devices()`` is a TPU with the cell's number of
chips, builds the federation from the seed, warms up, measures, and prints
one JSON object as the last line of its output.  Rounds per second, the
loss curve, compile seconds and cache hits go on earlier lines.  What a
cell is made of is found by name from ``BENCHMARK.json``
(``benchmarks/harness/spec.py``).
"""

import time

_T_START = time.perf_counter()     # before the imports: they are set-up too

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _ROOT)
    from benchmarks.harness import runner

    raise SystemExit(runner.main(sys.argv[1:], _ROOT, _T_START))
