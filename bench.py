"""Driver entry: headline benchmark (see colearn_federated_learning_tpu/bench.py).

Prints ONE JSON line naming the device it ran on; exits non-zero without
one when jax offers no accelerator.
"""

from colearn_federated_learning_tpu.bench import main

if __name__ == "__main__":
    raise SystemExit(main())
