"""CPU rehearsals of the benchmark.  Not collected by tier-1 (which runs
``tests/``): ``python -m pytest benchmarks/tests -q``.

jax is held to the CPU before it is imported; the cells themselves run in
child processes (``drive.py``), each with as many virtual CPU devices as
its cell has chips.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
