"""fed/lora.py: rank-r adapter federation — partition-rule targeting,
apply/merge math (incl. tp=2 sharded merge), factor-fold bitwise parity
(flat + aggregator-tree partials), secure-agg-over-factors exactness,
validate_robustness rejection matrix, one-compile-signature factor
training, and end-to-end merge parity on the socket plane."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from colearn_federated_learning_tpu.comm.aggregation import StreamingFolder
from colearn_federated_learning_tpu.comm.broker import MessageBroker
from colearn_federated_learning_tpu.comm.coordinator import (
    FederatedCoordinator,
)
from colearn_federated_learning_tpu.comm.worker import DeviceWorker
from colearn_federated_learning_tpu.fed import local as local_lib
from colearn_federated_learning_tpu.fed import lora
from colearn_federated_learning_tpu.fed import setup as setup_lib
from colearn_federated_learning_tpu.models import registry as model_registry
from colearn_federated_learning_tpu.parallel import partition
from colearn_federated_learning_tpu.telemetry import runtime
from colearn_federated_learning_tpu.utils.config import (
    ModelConfig,
    validate_robustness,
)
from tests.test_comm import _config, _run_federation

RANK, ALPHA = 4, 16.0


@pytest.fixture(scope="module")
def bert_params():
    """Real tiny-BERT params: targeting must be exercised against the
    actual flax param paths the partition rules were written for."""
    cfg = ModelConfig(name="bert", num_classes=4, width=32, depth=2,
                      num_heads=2, seq_len=64, vocab_size=2000)
    model = model_registry.build_model(cfg)
    return model_registry.init_params(
        model, jnp.zeros((1, 64), jnp.int32), jax.random.PRNGKey(0))


def _rand_factors(params, key=7):
    """Factor tree with BOTH A and B random — exercises nonzero merges."""
    rng = np.random.default_rng(key)
    return jax.tree.map(
        lambda f: rng.standard_normal(f.shape).astype(np.float32),
        jax.tree.map(np.asarray,
                     lora.init_factors(params, RANK, model_name="bert")))


def _tree_bytes(tree):
    return [np.asarray(l).tobytes() for l in jax.tree.leaves(tree)]


# ------------------------------------------------------------ targeting ----
def test_targeting_follows_partition_rules(bert_params):
    targets = lora.target_paths(bert_params, model_name="bert")
    # Adapted: vocab embedding, every block's attention QKV/out and MLP
    # up/down kernels — 1 + 2 blocks * 6 matrices.
    assert "Embed_0/embedding" in targets
    for blk in ("TransformerBlock_0", "TransformerBlock_1"):
        for mat in ("MultiHeadAttention_0/query/kernel",
                    "MultiHeadAttention_0/key/kernel",
                    "MultiHeadAttention_0/value/kernel",
                    "MultiHeadAttention_0/out/kernel",
                    "Dense_0/kernel", "Dense_1/kernel"):
            assert f"{blk}/{mat}" in targets
    assert len(targets) == 13
    # Frozen: classifier head, norms, position embedding, and every bias
    # (reshaped-head attention biases are 2-D but have no low-rank
    # structure worth r*(m+n) bytes).
    assert "Dense_0/kernel" not in targets
    assert "pos_embed" not in targets
    assert not any("LayerNorm" in p for p in targets)
    assert not any(p.endswith("bias") for p in targets)


def test_split_point_minimizes_factor_bytes():
    assert lora.split_point((2000, 32)) == 1
    assert lora.factor_dims((2000, 32)) == (2000, 32)
    # (32, 2, 16): k=1 costs 32+32, k=2 costs 64+16 -> 80; split low.
    assert lora.split_point((32, 2, 16)) == 1
    assert lora.factor_dims((32, 2, 16)) == (32, 32)
    # (2, 16, 32): k=2 costs 32+32 beats k=1's 2+512.
    assert lora.split_point((2, 16, 32)) == 2
    assert lora.factor_dims((2, 16, 32)) == (32, 32)


def test_init_factors_identity_at_round_zero(bert_params):
    f = lora.init_factors(bert_params, RANK, key=jax.random.PRNGKey(3),
                          model_name="bert")
    idx = lora.factor_index(f)
    assert len(idx) == 13
    for a, b in idx.values():
        assert np.all(np.asarray(b) == 0.0)        # B starts zero
        assert np.any(np.asarray(a) != 0.0)        # A is seeded
    # B=0 -> the adapted model IS the base model, bitwise.
    assert _tree_bytes(lora.apply_adapters(bert_params, f, ALPHA, RANK)) \
        == _tree_bytes(bert_params)
    # key=None builds the all-zeros template (worker/bench shape source).
    tmpl = lora.init_factors(bert_params, RANK, model_name="bert")
    assert all(np.all(np.asarray(l) == 0.0) for l in jax.tree.leaves(tmpl))


def test_merge_matches_manual_oracle(bert_params):
    factors = _rand_factors(bert_params)
    merged = jax.tree.map(np.asarray,
                          lora.merge_adapters(bert_params, factors,
                                              ALPHA, RANK))
    idx = lora.factor_index(factors)
    targets = lora.target_paths(bert_params, model_name="bert")
    flat = {partition.path_str(p): np.asarray(l) for p, l in
            jax.tree_util.tree_leaves_with_path(bert_params)}
    mflat = {partition.path_str(p): np.asarray(l) for p, l in
             jax.tree_util.tree_leaves_with_path(merged)}
    for path, w in flat.items():
        if path in targets:
            a, b = idx[path]
            delta = (np.asarray(b, np.float32) @ np.asarray(a, np.float32)
                     ).reshape(w.shape) * (ALPHA / RANK)
            np.testing.assert_allclose(mflat[path], w + delta,
                                       rtol=1e-5, atol=1e-6)
        else:
            # Non-adapted leaves pass through bitwise.
            assert mflat[path].tobytes() == w.tobytes()


def test_reset_keeps_a_zeroes_b(bert_params):
    factors = _rand_factors(bert_params)
    reset = lora.reset_factors(factors)
    for path, (a, b) in lora.factor_index(reset).items():
        assert np.all(np.asarray(b) == 0.0)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(lora.factor_index(factors)[path][0]))
    # Post-reset adapters are the identity again.
    assert _tree_bytes(lora.apply_adapters(bert_params, reset, ALPHA, RANK)) \
        == _tree_bytes(bert_params)


def test_sharded_merge_parity_tp2(bert_params):
    """The coordinator's jitted shard-wise merge on a tp=2 server equals
    the host oracle — no full-tree gather needed for correctness."""
    devs = jax.devices("cpu")
    if len(devs) < 2:
        pytest.skip("needs the forced 8-device CPU host")
    pl = partition.make_server_placement(bert_params, 2, "model", "bert",
                                         devices=devs[:2])
    assert pl is not None
    factors = _rand_factors(bert_params)
    merge = jax.jit(lambda p, f: lora.merge_adapters(p, f, ALPHA, RANK))
    out = merge(pl.shard(bert_params), factors)
    host = jax.tree.map(np.asarray, partition.host_tree(out))
    oracle = jax.tree.map(np.asarray,
                          lora.merge_adapters(bert_params, factors,
                                              ALPHA, RANK))
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(oracle)):
        # The tp=2 merge contracts B·A in another order than the host:
        # float32 rounding (258 of 64,000 elements, <= 1.25e-6 at 10).
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- factor folding ----
def _factor_updates(shapes, n):
    out = []
    for i in range(n):
        rng = np.random.default_rng(100 + i)
        d = jax.tree.map(
            lambda f: rng.standard_normal(f.shape).astype(np.float32),
            shapes)
        out.append(({"client_id": str(i), "weight": 1.0 + 0.25 * i,
                     "mean_loss": 0.5 + 0.1 * i}, d))
    return out


def test_factor_fold_bitwise_arrival_invariant(bert_params):
    """StreamingFolder over the FACTOR template: any arrival order
    finalizes to the bitwise cohort-order sum (the shape-generic fold the
    coordinator builds under lora)."""
    shapes = jax.tree.map(np.asarray,
                          lora.init_factors(bert_params, RANK,
                                            model_name="bert"))
    order = [str(i) for i in range(4)]
    updates = _factor_updates(shapes, 4)
    shuffled = list(updates)
    random.Random(13).shuffle(shuffled)

    ref = StreamingFolder(shapes, order=order)
    shf = StreamingFolder(shapes, order=order)
    for meta, d in updates:
        ref.add(dict(meta), jax.tree.map(np.copy, d))
    for meta, d in shuffled:
        shf.add(dict(meta), jax.tree.map(np.copy, d))
    m_ref, w_ref, l_ref = ref.mean()
    m_shf, w_shf, l_shf = shf.mean()
    assert w_ref == w_shf and l_ref == l_shf
    assert _tree_bytes(m_ref) == _tree_bytes(m_shf)


def test_factor_fold_aggregator_partials_bitwise(bert_params):
    """Aggregator-tree composition over factor trees: slice folds shipped
    as partials combine at the root bitwise identically to a flat cohort
    fold built with the same slice layout (what the tier does when meta
    carries the lora marker)."""
    shapes = jax.tree.map(np.asarray,
                          lora.init_factors(bert_params, RANK,
                                            model_name="bert"))
    order = [str(i) for i in range(4)]
    updates = _factor_updates(shapes, 4)

    flat = StreamingFolder(shapes, order=order,
                           slices=[order[:2], order[2:]])
    for meta, d in updates:
        flat.add(dict(meta), jax.tree.map(np.copy, d))

    root = StreamingFolder(shapes, order=["agg0", "agg1"])
    for key, sl in (("agg0", updates[:2]), ("agg1", updates[2:])):
        sub = StreamingFolder(shapes, order=[m["client_id"] for m, _ in sl])
        for meta, d in sl:
            sub.add(dict(meta), jax.tree.map(np.copy, d))
        sub.finalize()
        root.add_partial(key, sub.total_w, sub.wsum, sub.loss_sum,
                         count=sub.count)
    m_flat, w_flat, l_flat = flat.mean()
    m_root, w_root, l_root = root.mean()
    assert w_flat == w_root and l_flat == l_root
    assert root.count == flat.count == 4
    assert _tree_bytes(m_flat) == _tree_bytes(m_root)


# ------------------------------------------------------------ validation ----
def _fed(**kw):
    base = dict(strategy="fedavg", lora_rank=4, lora_alpha=16.0,
                lora_merge_every=2)
    base.update(kw)
    return _config(num_clients=2, **base)


@pytest.mark.parametrize("bad", [
    dict(lora_rank=-1),
    dict(lora_alpha=0.0),
    dict(lora_alpha=-2.0),
    dict(lora_merge_every=0),
    dict(compress_down="int8"),
    dict(strategy="fedadam"),
    dict(strategy="fedyogi"),
])
def test_validate_robustness_rejects_lora_conflicts(bad):
    with pytest.raises(ValueError):
        validate_robustness(_fed(**bad))


@pytest.mark.parametrize("ok", [
    dict(),
    dict(strategy="fedprox", prox_mu=0.01),
    dict(compress="topk"),
    dict(compress="topk8", compress_feedback=True),
    dict(secure_agg=True),
])
def test_validate_robustness_allows_lora_compositions(ok):
    validate_robustness(_fed(**ok))   # must not raise


def test_dense_trainer_refuses_lora_config():
    """In-process planes (engine/offline/programs) reach the DENSE
    trainer; silently ignoring lora_rank there would train the full
    model while claiming adapter federation."""
    cfg = _fed()
    model = model_registry.build_model(cfg.model)
    with pytest.raises(ValueError, match="socket"):
        setup_lib.local_trainer_for_config(cfg, model.apply, 64)
    # fleetsim's documented dense-dynamics decoupling stays allowed.
    update, _ = setup_lib.local_trainer_for_config(cfg, model.apply, 64,
                                                   lora_dense_ok=True)
    assert callable(update)


# ------------------------------------------------- factor-only training ----
def test_lora_local_update_one_compile_signature():
    """The jitted factor trainer holds ONE XLA signature across rounds:
    factor values change, shapes never do — the compile-cost contract the
    wire plane's round latency depends on."""
    cfg = _fed()
    model = model_registry.build_model(cfg.model)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (64, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(np.random.default_rng(1).integers(0, 10, 64))
    params = model_registry.init_params(model, x[:16],
                                        jax.random.PRNGKey(0))
    factors = lora.init_factors(params, RANK, key=jax.random.PRNGKey(2),
                                model_name=cfg.model.name)
    assert lora.count_factor_params(factors) > 0
    optimizer = local_lib.make_optimizer(0.1, 0.0, "sgd")
    update = local_lib.make_lora_local_update(
        model.apply, optimizer, num_steps=3, batch_size=16,
        rank=RANK, alpha=ALPHA)
    tracked = runtime.CompileTracker(jax.jit(update), name="lora_local")

    f = factors
    for rnd in range(3):
        res = tracked(params, f, x, y, jnp.asarray(64, jnp.int32),
                      jax.random.PRNGKey(10 + rnd),
                      jnp.asarray(3, jnp.int32))
        assert bool(res.completed) and np.isfinite(float(res.mean_loss))
        # The reply is factor-shaped (O(r*d)), not params-shaped — and a
        # real step moved the factors.
        assert jax.tree.structure(res.delta) == jax.tree.structure(factors)
        assert any(np.any(np.asarray(l) != 0.0)
                   for l in jax.tree.leaves(res.delta))
        f = jax.tree.map(jnp.add, f, res.delta)
    assert tracked.compiles == 1
    assert tracked.recompiles == 0


# ------------------------------------------------------- socket e2e ----
def _run_lora_federation(cfg, n, rounds):
    """Like tests.test_comm._run_federation but also returns the
    coordinator's factor tree (host numpy) alongside params/records."""
    with MessageBroker() as broker:
        workers = [
            DeviceWorker(cfg, i, broker.host, broker.port).start()
            for i in range(n)
        ]
        try:
            coord = FederatedCoordinator(cfg, broker.host, broker.port,
                                         round_timeout=60.0,
                                         want_evaluator=False)
            coord.enroll(min_devices=n, timeout=20.0)
            coord.trainers.sort(key=lambda d: int(d.device_id))
            for w in workers:
                w.await_role(timeout=10.0)
            recs = [coord.run_round() for _ in range(rounds)]
            params = jax.tree.map(np.asarray, coord.server_state.params)
            factors = jax.tree.map(np.asarray, coord._factors)
            coord.close()
            return recs, params, factors
        finally:
            for w in workers:
                w.stop()


def test_socket_lora_merge_parity_oracle():
    """Federated-run-then-merge == manual oracle: a no-merge run exposes
    the aggregated factors; a merge_every=2 twin (identical training —
    the merge lands AFTER round 2's broadcast) must equal
    merge_adapters(frozen base, those factors), with B re-zeroed and A
    kept."""
    cfg_hold = _fed(momentum=0.0, lr=0.05, lora_merge_every=100)
    recs_h, params_h, factors_h = _run_lora_federation(cfg_hold, 2, 2)
    assert all(r["completed"] == 2 for r in recs_h)
    assert all(not r["lora_merged"] for r in recs_h)
    assert all(np.isfinite(r["train_loss"]) for r in recs_h)
    # Factor uplink savings are real and priced per folded update.
    assert all(r["bytes_saved_uplink"] > 0 for r in recs_h)
    # No merge -> the base NEVER moves: bitwise equal to a fresh init.
    init = jax.tree.map(np.asarray, setup_lib.init_global_params(cfg_hold))
    assert _tree_bytes(params_h) == _tree_bytes(init)
    # ...but the factors did (training happened).
    assert any(np.any(np.asarray(b) != 0.0)
               for _, b in lora.factor_index(factors_h).values())

    cfg_merge = _fed(momentum=0.0, lr=0.05, lora_merge_every=2)
    recs_m, params_m, factors_m = _run_lora_federation(cfg_merge, 2, 2)
    assert [r["lora_merged"] for r in recs_m] == [False, True]
    oracle = jax.tree.map(
        np.asarray, lora.merge_adapters(params_h, factors_h, ALPHA, RANK))
    for a, b in zip(jax.tree.leaves(params_m), jax.tree.leaves(oracle)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    # Post-merge factor state: B zeroed (fresh adapting basis), A kept.
    for path, (a, b) in lora.factor_index(factors_m).items():
        assert np.all(np.asarray(b) == 0.0)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(lora.factor_index(factors_h)[path][0]))


def test_socket_secure_agg_over_factors_exact():
    """secure_agg masks the FACTOR tree: a masked lora federation must
    land on the plain lora run's aggregate (pairwise masks cancel over
    the factor-shaped fold template)."""
    cfg = _fed(momentum=0.0, lr=0.05, lora_merge_every=2)
    recs_p, params_p, factors_p = _run_lora_federation(cfg, 2, 2)

    cfg_sec = _fed(momentum=0.0, lr=0.05, lora_merge_every=2,
                   secure_agg=True)
    recs_s, params_s, factors_s = _run_lora_federation(cfg_sec, 2, 2)
    assert all(r["completed"] == 2 for r in recs_p + recs_s)
    assert recs_s[-1]["lora_merged"]
    for a, b in zip(jax.tree.leaves(factors_p), jax.tree.leaves(factors_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    for a, b in zip(jax.tree.leaves(params_p), jax.tree.leaves(params_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_lora_off_round_records_unchanged():
    """lora off -> round records carry NO adapter keys (and no uplink
    savings keys on an uncompressed run): the default wire plane is
    byte-identical to pre-lora records."""
    recs, _, _ = _run_federation(_config(num_clients=2), 2, rounds=1)
    for rec in recs:
        assert "lora_merged" not in rec
        assert "bytes_saved_uplink" not in rec
        assert "uplink_densify_avoided" not in rec
