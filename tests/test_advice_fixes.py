"""Regressions for the round-2 ADVICE.md findings: scaffold momentum bias,
profiler leak on early fit() exit, prime-count mesh factoring, shared round
deadline in the socket coordinator, versioned native library filename."""

import threading
import time

import numpy as np
import pytest

from colearn_federated_learning_tpu.comm.broker import MessageBroker
from colearn_federated_learning_tpu.comm.coordinator import FederatedCoordinator
from colearn_federated_learning_tpu.comm.worker import DeviceWorker
from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.parallel import factor_devices, make_mesh
from colearn_federated_learning_tpu.utils.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    ModelConfig,
    RunConfig,
)


def _cfg(**fed_kw):
    fed = dict(strategy="fedavg", rounds=4, cohort_size=0, local_steps=2,
               batch_size=16, lr=0.05, momentum=0.9)
    fed.update(fed_kw)
    return ExperimentConfig(
        data=DataConfig(dataset="mnist_tiny", num_clients=4, partition="iid"),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=16, depth=1),
        fed=FedConfig(**fed),
        run=RunConfig(name="advice", backend="cpu"),
    )


# ---- 1. scaffold momentum guard -------------------------------------------
def test_scaffold_rejects_momentum():
    """Option-II variate refresh is only the mean corrected gradient under
    vanilla SGD; the default momentum=0.9 must be rejected, not silently
    biased."""
    with pytest.raises(ValueError, match="momentum"):
        FederatedLearner(_cfg(strategy="scaffold", momentum=0.9))
    # momentum=0.0 still builds
    FederatedLearner(_cfg(strategy="scaffold", momentum=0.0))


# ---- 2. profiler closed on early exit from fit() --------------------------
def test_profiler_closed_on_fit_exception(tmp_path):
    import dataclasses

    cfg = _cfg()
    cfg = dataclasses.replace(
        cfg, run=dataclasses.replace(cfg.run, profile_dir=str(tmp_path)),
    )
    learner = FederatedLearner(cfg)

    def explode(rec):
        # Round 1 is INSIDE the default trace window (rounds 1..2): the
        # profiler is active when this raises.
        if rec["round"] == 1:
            raise RuntimeError("mid-window failure")

    with pytest.raises(RuntimeError, match="mid-window"):
        learner.fit(rounds=3, log_fn=explode)
    # If fit() leaked the active trace, the next window's start_trace would
    # raise "profiler already started".
    learner.fit(rounds=2)


# ---- 3. mesh factoring for 2 / prime device counts ------------------------
def test_factor_devices_small_and_prime():
    # The trailing (seq) axis takes the whole remainder when it is prime —
    # (1, n) still gives ring attention a real ring; (n, 1) broke it.
    assert factor_devices(2, 2) == (1, 2)
    assert factor_devices(3, 2) == (1, 3)
    assert factor_devices(7, 2) == (1, 7)
    assert factor_devices(8, 2) == (4, 2)
    assert factor_devices(1, 2) == (1, 1)


def test_make_mesh_two_devices_ring_axis(cpu_devices):
    m = make_mesh(("clients", "seq"), devices=cpu_devices[:2])
    assert m.shape == {"clients": 1, "seq": 2}


# ---- 4. shared round deadline ---------------------------------------------
def test_round_timeout_is_shared_not_per_future():
    """Three of four workers hang: the round must cost ~round_timeout, not
    3 x round_timeout (the old sequential per-future collection)."""
    cfg = _cfg(local_steps=1)
    with MessageBroker() as broker:
        workers = [
            DeviceWorker(cfg, i, broker.host, broker.port).start()
            for i in range(4)
        ]
        try:
            coord = FederatedCoordinator(cfg, broker.host, broker.port,
                                         round_timeout=60.0,
                                         want_evaluator=False)
            coord.enroll(min_devices=4, timeout=20.0)
            warm = coord.run_round()                 # compile everywhere
            assert warm["completed"] == 4

            release = threading.Event()
            originals = {}
            for w in workers[1:]:
                orig = w._train
                originals[w] = orig

                def hang(round_idx, params, _orig=orig):
                    release.wait(timeout=30.0)
                    return _orig(round_idx, params)

                w._train = hang
            coord.round_timeout = 1.5
            t0 = time.perf_counter()
            rec = coord.run_round()
            elapsed = time.perf_counter() - t0
            release.set()
            assert rec["completed"] == 1
            assert sorted(rec["dropped"]) == ["1", "2", "3"]
            assert np.isfinite(rec["train_loss"])
            # one shared deadline: well under 3 sequential timeouts (4.5s)
            assert elapsed < 3.5, f"round took {elapsed:.1f}s"
            coord.close()
        finally:
            for w in workers:
                w.stop()


# ---- 5. native library filename keyed on ABI version + source content ----
def test_native_lib_filename_carries_abi_version_and_source_digest(
        monkeypatch, tmp_path):
    from colearn_federated_learning_tpu.native import build as build_mod

    name = build_mod.lib_path().name
    assert f"v{build_mod.ABI_VERSION}_" in name
    assert build_mod.lib_path().name == name          # deterministic
    # A copied tree can carry a newer .so than its sources (git does not
    # keep mtimes): only a change of CONTENT may select another binary,
    # and it must.
    edited = tmp_path / "gather.cpp"
    edited.write_bytes(build_mod.SOURCES[0].read_bytes() + b"\n// edit\n")
    monkeypatch.setattr(build_mod, "SOURCES",
                        [edited, *build_mod.SOURCES[1:]])
    assert build_mod.lib_path().name != name
    assert not build_mod.lib_path().exists()
