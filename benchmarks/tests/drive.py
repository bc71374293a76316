"""Child process of the rehearsals: one run of one cell on the CPU.

    python drive.py <root> <workload> <seed> <seconds> <trace>

``run.py`` with the one thing a test may change and a user may not: the
platform the run is allowed on.
"""

import time

_T_START = time.perf_counter()

import sys  # noqa: E402

if __name__ == "__main__":
    root, workload, seed, seconds, trace = sys.argv[1:6]
    sys.path.insert(0, root)
    from benchmarks.harness import runner

    raise SystemExit(runner.run_cell(
        root, workload, int(seed), float(seconds), trace == "1", _T_START,
        platform="cpu"))
