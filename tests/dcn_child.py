"""Child process for the multi-host (DCN) hybrid-mesh integration test.

Launched twice by tests/test_dcn.py with ``python dcn_child.py <pid> <port>``:
initializes 2-process distributed JAX over virtual CPU devices, builds the
DCN-aware hybrid mesh through parallel/mesh.make_mesh, runs one
cross-process psum and one full engine round, and prints machine-checkable
lines the parent asserts on.
"""

import os
import sys

pid = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from colearn_federated_learning_tpu.fed.engine import (  # noqa: E402
    FederatedLearner,
)
from colearn_federated_learning_tpu.parallel.mesh import make_mesh  # noqa: E402
from colearn_federated_learning_tpu.utils.config import (  # noqa: E402
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)

mesh = make_mesh(("clients",))
print(pid, "MESHLAYOUT",
      ",".join(str(d.process_index) for d in mesh.devices.ravel()),
      flush=True)

f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "clients"),
                          mesh=mesh, in_specs=P("clients"), out_specs=P()))
xs = jax.device_put(jnp.arange(8, dtype=jnp.float32),
                    NamedSharding(mesh, P("clients")))
print(pid, "PSUM", float(np.asarray(f(xs).addressable_data(0))), flush=True)

cfg = ExperimentConfig(
    data=DataConfig(dataset="mnist_tiny", num_clients=8, partition="iid",
                    max_examples_per_client=32),
    model=ModelConfig(name="mlp", num_classes=10, hidden_dim=16, depth=2),
    fed=FedConfig(strategy="fedavg", rounds=2, cohort_size=0, local_steps=2,
                  batch_size=8, lr=0.1, momentum=0.9),
    run=RunConfig(name="dcn_test", backend="cpu"),
)
learner = FederatedLearner(cfg, mesh=mesh)
rec = learner.run_round()
print(pid, "ROUND", rec["train_loss"], flush=True)
