"""analysis/: one positive + one suppression fixture per rule
(CL001–CL016 and CL023; CL017–CL021 live in test_lint_concurrency.py),
the noqa/baseline machinery (CL000 dead suppressions,
line-shift-stable fingerprints), the `colearn lint` CLI exit codes, the
labeled-counter roll-up the registry grew for per-device attribution,
and the tier-1 self-check that the installed package is lint-clean."""

import json
import os
import textwrap

import pytest

from colearn_federated_learning_tpu.analysis.engine import (
    LintConfig,
    LintEngine,
    write_baseline,
)
from colearn_federated_learning_tpu.cli import main as cli_main
from colearn_federated_learning_tpu.telemetry import registry as telemetry_registry
from colearn_federated_learning_tpu.telemetry.registry import MetricsRegistry


def run_lint(tmp_path, source, relpath="pkg/comm/mod.py", rules=None,
             baseline=""):
    """Lint one fixture file placed at ``relpath`` under a scratch root
    (the directory names drive the scoped rules: comm/, faults/)."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    eng = LintEngine(config=LintConfig(enable=rules), root=str(tmp_path))
    return eng.run([str(path)], baseline_path=baseline)


def rule_ids(result):
    return sorted({f.rule for f in result.findings})


# ------------------------------------------------------------- CL001 ----
def test_cl001_flags_print_in_jit_decorated_function(tmp_path):
    res = run_lint(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            print("tracing", x)
            return x
    """, relpath="pkg/fed/mod.py", rules=["CL001"])
    assert rule_ids(res) == ["CL001"]
    assert res.exit_code == 1


def test_cl001_flags_time_call_in_jit_call_site_target(tmp_path):
    res = run_lint(tmp_path, """
        import time
        from jax import jit

        def train(x):
            t0 = time.perf_counter()
            return x + t0

        train_fast = jit(train)
    """, relpath="pkg/fed/mod.py")
    assert rule_ids(res) == ["CL001"]


def test_cl001_suppression(tmp_path):
    res = run_lint(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            print("trace marker")  # colearn: noqa(CL001): test fixture
            return x
    """, relpath="pkg/fed/mod.py", rules=["CL001"])
    assert res.findings == [] and res.suppressed == 1


def test_cl001_ignores_untraced_functions(tmp_path):
    # Scoped to CL001: a host-side stdout print is fine by THIS rule
    # (CL010 has its own opinion about library stdout).
    res = run_lint(tmp_path, """
        def host_side(x):
            print(x)
            return x
    """, relpath="pkg/fed/mod.py", rules=["CL001"])
    assert res.findings == []


# ------------------------------------------------------------- CL002 ----
def test_cl002_flags_untimed_client_and_recv_in_comm(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.broker import BrokerClient

        def attach(host, port):
            return BrokerClient(host, port)

        def drain(sock):
            return sock.recv(4)
    """)
    assert rule_ids(res) == ["CL002"]
    assert len(res.findings) == 2


def test_cl002_passes_timeout_kwarg_and_timeout_bearing_function(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.broker import BrokerClient

        def attach(host, port):
            return BrokerClient(host, port, timeout=5.0)

        def drain(sock, timeout):
            sock.settimeout(timeout)
            return sock.recv(4)
    """)
    assert res.findings == []


def test_cl002_only_applies_under_comm(tmp_path):
    res = run_lint(tmp_path, """
        def drain(sock):
            return sock.recv(4)
    """, relpath="pkg/fed/mod.py")
    assert res.findings == []


def test_cl002_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def accept_forever(srv):
            return srv.accept()  # colearn: noqa(CL002): test fixture
    """)
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL003 ----
def test_cl003_flags_bare_except_and_swallowed_handler(tmp_path):
    res = run_lint(tmp_path, """
        def teardown(sock):
            try:
                sock.close()
            except OSError:
                pass
            try:
                sock.detach()
            except:
                return None
    """)
    assert rule_ids(res) == ["CL003"]
    assert len(res.findings) == 2


def test_cl003_allows_handlers_with_real_bodies(tmp_path):
    res = run_lint(tmp_path, """
        def teardown(sock, counter):
            try:
                sock.close()
            except OSError:
                counter.inc()
    """)
    assert res.findings == []


def test_cl003_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def teardown(sock):
            try:
                sock.close()
            except OSError:  # colearn: noqa(CL003): test fixture
                pass
    """)
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL004 ----
def test_cl004_flags_wall_clock_and_unseeded_rng_in_faults(tmp_path):
    res = run_lint(tmp_path, """
        import random
        import time

        def jitter():
            return random.random() + time.time()
    """, relpath="pkg/faults/mod.py")
    assert rule_ids(res) == ["CL004"]
    assert len(res.findings) == 2


def test_cl004_allows_seeded_rng_and_monotonic(tmp_path):
    res = run_lint(tmp_path, """
        import random
        import time

        def jitter(seed):
            rng = random.Random(seed)
            return rng.uniform(0, 1), time.monotonic()
    """, relpath="pkg/faults/mod.py")
    assert res.findings == []


def test_cl004_suppression(tmp_path):
    res = run_lint(tmp_path, """
        import time

        def stamp():
            return time.time()  # colearn: noqa(CL004): test fixture
    """, relpath="pkg/faults/mod.py")
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL005 ----
def test_cl005_flags_typoed_counter_name(tmp_path):
    res = run_lint(tmp_path, """
        def bump(registry):
            registry.counter("comm.retry_totl").inc()
    """, relpath="pkg/fed/mod.py")
    assert rule_ids(res) == ["CL005"]


def test_cl005_passes_catalog_names_and_wildcard_fstrings(tmp_path):
    res = run_lint(tmp_path, """
        def bump(registry, kind):
            registry.counter("comm.retry_total").inc()
            registry.counter(f"fault.injected.{kind}").inc()
            registry.histogram("fed.round_time_s").observe(1.0)
    """, relpath="pkg/fed/mod.py")
    assert res.findings == []


def test_cl005_flags_fstring_with_unknown_prefix(tmp_path):
    res = run_lint(tmp_path, """
        def bump(registry, kind):
            registry.counter(f"surprise.{kind}").inc()
    """, relpath="pkg/fed/mod.py")
    assert rule_ids(res) == ["CL005"]


def test_cl005_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def bump(registry):
            registry.counter("scratch.local_only").inc()  # colearn: noqa(CL005): test fixture
    """, relpath="pkg/fed/mod.py")
    assert res.findings == [] and res.suppressed == 1


def test_cl005_flags_non_literal_metric_name(tmp_path):
    # A plain variable slips past catalog validation entirely — the
    # hardened rule reports it instead of silently passing.
    res = run_lint(tmp_path, """
        def bump(registry, name):
            registry.counter(name).inc()
    """, relpath="pkg/fed/mod.py")
    assert rule_ids(res) == ["CL005"]
    assert "non-literal" in res.findings[0].message


def test_cl005_non_literal_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def snapshot(registry, names):
            return {n: registry.counter(n).value  # colearn: noqa(CL005): test fixture
                    for n in names}
    """, relpath="pkg/fed/mod.py")
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL006 ----
def test_cl006_flags_host_sync_in_traced_function(tmp_path):
    res = run_lint(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            return float(x)
    """, relpath="pkg/fed/mod.py")
    assert rule_ids(res) == ["CL006"]


def test_cl006_flags_block_until_ready_in_hot_loop(tmp_path):
    res = run_lint(tmp_path, """
        def fit(batches):
            for b in batches:  # colearn: hot
                b.result.block_until_ready()
    """, relpath="pkg/fed/mod.py")
    assert rule_ids(res) == ["CL006"]


def test_cl006_suppression(tmp_path):
    res = run_lint(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            return float(x)  # colearn: noqa(CL006): test fixture
    """, relpath="pkg/fed/mod.py")
    assert res.findings == [] and res.suppressed == 1


def test_cl006_allows_host_sync_outside_hot_paths(tmp_path):
    res = run_lint(tmp_path, """
        def summarize(x):
            return float(x)
    """, relpath="pkg/fed/mod.py")
    assert res.findings == []


# ------------------------------------------------------------- CL007 ----
def test_cl007_flags_per_request_encode_in_hot_fanout_loop(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.utils.serialization import pytree_to_bytes

        def broadcast(devs, params):
            for d in devs:  # colearn: hot
                d.send(pytree_to_bytes(params))
    """)
    assert rule_ids(res) == ["CL007"]


def test_cl007_allows_encode_hoisted_before_the_loop(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.utils.serialization import pytree_to_bytes

        def broadcast(devs, params):
            body = pytree_to_bytes(params)
            for d in devs:  # colearn: hot
                d.send(body)
    """)
    assert res.findings == []


def test_cl007_ignores_loops_not_marked_hot(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.utils.serialization import pytree_to_bytes

        def snapshot_all(trees):
            for t in trees:
                yield pytree_to_bytes(t)
    """)
    assert res.findings == []


def test_cl007_suppression(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.utils.serialization import save_pytree_npz

        def dump(devs, params):
            for d in devs:  # colearn: hot
                save_pytree_npz(d.path, params)  # colearn: noqa(CL007): test fixture
    """)
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL008 ----
def test_cl008_flags_in_place_exchange_writes_in_fed(tmp_path):
    res = run_lint(tmp_path, """
        import numpy as np
        from pkg.utils.serialization import save_pytree_npz

        def publish(path, tree):
            save_pytree_npz(path, tree)

        def manifest(path, names):
            with open(path, "w") as f:
                f.write("\\n".join(names))

        def raw(path, arrs):
            np.savez(path, **arrs)
    """, relpath="pkg/fed/exchange.py")
    assert rule_ids(res) == ["CL008"]
    assert len(res.findings) == 3


def test_cl008_allows_temp_plus_replace_in_same_function(tmp_path):
    res = run_lint(tmp_path, """
        import os
        from pkg.utils.serialization import save_pytree_npz

        def publish(path, tree):
            tmp = path + ".tmp"
            save_pytree_npz(tmp, tree)
            os.replace(tmp, path)
    """, relpath="pkg/fed/exchange.py")
    assert res.findings == []


def test_cl008_ignores_writes_outside_fed(tmp_path):
    res = run_lint(tmp_path, """
        def snapshot(path, blob):
            with open(path, "wb") as f:
                f.write(blob)
    """, relpath="pkg/telemetry/dump.py")
    assert res.findings == []


def test_cl008_ignores_reads_and_appends(tmp_path):
    res = run_lint(tmp_path, """
        def load(path):
            with open(path, "rb") as f:
                return f.read()

        def journal(path, line):
            with open(path, "a") as f:
                f.write(line)
    """, relpath="pkg/fed/offline.py")
    assert res.findings == []


def test_cl008_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def scratch(path, blob):
            with open(path, "wb") as f:  # colearn: noqa(CL008): test fixture
                f.write(blob)
    """, relpath="pkg/fed/exchange.py")
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL009 ----
def test_cl009_flags_per_device_loop_in_hot_path(tmp_path):
    res = run_lint(tmp_path, """
        def run_round(cohort_ids, train_one):
            out = []
            for device_id in cohort_ids:  # colearn: hot
                out.append(train_one(device_id))
            return out
    """, relpath="pkg/fleetsim/mod.py")
    assert rule_ids(res) == ["CL009"]
    assert res.exit_code == 1


def test_cl009_flags_local_update_call_per_iteration(tmp_path):
    res = run_lint(tmp_path, """
        def run_round(chunks, local_update, params):
            acc = None
            for chunk in chunks:  # colearn: hot
                acc = local_update(params, chunk)
            return acc
    """, relpath="pkg/fleetsim/sim.py")
    assert rule_ids(res) == ["CL009"]


def test_cl009_allows_chunk_loop(tmp_path):
    # The blessed shape: loop over CHUNK OFFSETS, one jitted vmapped
    # dispatch per chunk (fleetsim/sim.FleetSim.run_round).
    res = run_lint(tmp_path, """
        def run_round(n, chunk, chunk_fn, fold, acc):
            for lo in range(0, n, chunk):  # colearn: hot
                acc = fold(acc, chunk_fn(lo))
            return acc
    """, relpath="pkg/fleetsim/sim.py")
    assert res.findings == []


def test_cl009_ignores_unmarked_and_non_fleetsim_loops(tmp_path):
    src = """
        def setup(device_ids, probe):
            for device_id in device_ids:
                probe(device_id)

        def elsewhere(client_ids, send):
            for client_id in client_ids:  # colearn: hot
                send(client_id)
    """
    # Unmarked fleetsim loop: cold paths may iterate per device.
    res = run_lint(tmp_path, src.split("def elsewhere")[0],
                   relpath="pkg/fleetsim/population.py")
    assert res.findings == []
    # Marked per-client loop OUTSIDE fleetsim/: not CL009's business
    # (the comm fan-out has its own rules).
    res = run_lint(tmp_path, "def elsewhere" + src.split("def elsewhere")[1],
                   relpath="pkg/comm/mod.py")
    assert res.findings == []


def test_cl009_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def debug_round(cohort_ids, train_one):
            for device_id in cohort_ids:  # colearn: hot  # colearn: noqa(CL009): test fixture
                train_one(device_id)
    """, relpath="pkg/fleetsim/mod.py")
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL010 ----
def test_cl010_flags_print_to_stdout_in_library_code(tmp_path):
    res = run_lint(tmp_path, """
        def announce(port):
            print({"port": port})
    """, relpath="pkg/comm/mod.py")
    assert rule_ids(res) == ["CL010"]
    assert res.exit_code == 1


def test_cl010_flags_explicit_sys_stdout(tmp_path):
    res = run_lint(tmp_path, """
        import sys

        def announce(port):
            print(port, file=sys.stdout)
    """, relpath="pkg/fed/mod.py")
    assert rule_ids(res) == ["CL010"]


def test_cl010_allows_stderr_and_file_objects(tmp_path):
    res = run_lint(tmp_path, """
        import sys

        def announce(port, log):
            print(port, file=sys.stderr)
            print(port, file=log)
    """, relpath="pkg/comm/mod.py")
    assert res.findings == []


def test_cl010_exempts_cli_scripts_and_main_guards(tmp_path):
    src = """
        def report(x):
            print(x)
    """
    # cli.py IS the stdout contract (machine-readable summary lines);
    # scripts/ is operator tooling.
    assert run_lint(tmp_path, src, relpath="pkg/cli.py").findings == []
    assert run_lint(tmp_path, src,
                    relpath="pkg/scripts/tool.py").findings == []
    # __main__ guard: the module is being run AS a script.
    res = run_lint(tmp_path, """
        def build():
            return "x"

        if __name__ == "__main__":
            print(build())
    """, relpath="pkg/native/build.py")
    assert res.findings == []


def test_cl010_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def report(x):
            print(x)  # colearn: noqa(CL010): test fixture
    """, relpath="pkg/fed/mod.py")
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL011 ----
def test_cl011_flags_expander_call_per_pair(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.privacy.secure_agg import pairwise_mask

        def mask_all(update, key, me, partners, rnd):
            for p in partners:  # colearn: hot
                update = update + pairwise_mask(update, key, me, p, rnd)
            return update
    """, relpath="pkg/privacy/mod.py")
    assert rule_ids(res) == ["CL011"]
    assert res.exit_code == 1


def test_cl011_flags_per_pair_head_in_comm(tmp_path):
    res = run_lint(tmp_path, """
        def fold_masks(pair_rows, expand, acc):
            for pair in pair_rows:  # colearn: hot
                acc = acc + expand(pair)
            return acc
    """, relpath="pkg/comm/mod.py")
    assert rule_ids(res) == ["CL011"]


def test_cl011_allows_key_derivation_loop(tmp_path):
    # The sanctioned per-pair loop: deriving the KEY TABLE (one scalar
    # modexp per pair), which then feeds ONE *_with_keys dispatch.
    res = run_lint(tmp_path, """
        from pkg.comm.keyexchange import pair_prng_key, shared_secret
        from pkg.privacy.secure_agg import mask_update_with_keys

        def mask(update, priv, me, peers, pubs, signs, rnd):
            keys = []
            for p in peers:  # colearn: hot
                keys.append(pair_prng_key(shared_secret(priv, pubs[p]),
                                          me, p))
            return mask_update_with_keys(update, keys, signs, rnd)
    """, relpath="pkg/comm/worker.py")
    assert res.findings == []


def test_cl011_ignores_unmarked_and_out_of_scope_loops(tmp_path):
    src = """
        from pkg.privacy.secure_agg import pairwise_mask

        def cold(update, key, me, partners, rnd):
            for p in partners:
                update = update + pairwise_mask(update, key, me, p, rnd)
            return update
    """
    # Unmarked loop in privacy/: cold paths may iterate per pair.
    res = run_lint(tmp_path, src, relpath="pkg/privacy/mod.py")
    assert res.findings == []
    # Marked per-pair loop OUTSIDE privacy//comm/: not CL011's business.
    res = run_lint(tmp_path, """
        def sweep(pair_counts, probe):
            for pairs in pair_counts:  # colearn: hot
                probe(pairs)
    """, relpath="pkg/fleetsim/mod.py")
    assert res.findings == []


def test_cl011_suppression(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.privacy.secure_agg import mask_scalar

        def debug_mask(xs, key, me, partners, rnd):
            for p in partners:  # colearn: hot  # colearn: noqa(CL011): test fixture
                xs = mask_scalar(xs, key, me, p, rnd)
            return xs
    """, relpath="pkg/privacy/mod.py")
    assert res.findings == [] and res.suppressed == 1


def test_cl012_flags_device_get_in_hot_wire_path(tmp_path):
    res = run_lint(tmp_path, """
        import jax

        def encode_round(rnd, params, codec):
            with codec.span("serialize"):  # colearn: hot
                host = jax.device_get(params)
            return codec.pack(rnd, host)
    """, relpath="pkg/comm/downlink.py")
    assert rule_ids(res) == ["CL012"]
    assert res.exit_code == 1


def test_cl012_flags_tree_map_asarray_gather(tmp_path):
    # The full-tree gather idiom spelled via tree.map(np.asarray, ...):
    # every leaf is pulled whole to one host buffer.
    res = run_lint(tmp_path, """
        import jax
        import numpy as np

        def serialize(params, wire):  # colearn: hot
            host = jax.tree.map(np.asarray, params)
            return wire.pack(host)
    """, relpath="pkg/comm/coordinator.py")
    assert rule_ids(res) == ["CL012"]


def test_cl012_allows_per_shard_reads_and_cold_paths(tmp_path):
    # Per-shard host reads (the sanctioned replacement) don't trip it.
    res = run_lint(tmp_path, """
        import numpy as np

        def host_read(a):
            out = np.empty(a.shape, a.dtype)
            for sh in a.addressable_shards:  # colearn: hot
                out[sh.index] = np.asarray(sh.data)
            return out
    """, relpath="pkg/comm/downlink.py", rules=["CL012"])
    assert res.findings == []
    # Unmarked (cold) gather in comm/: eval paths may gather whole trees.
    res = run_lint(tmp_path, """
        import jax

        def evaluate(params, batch):
            return score(jax.device_get(params), batch)
    """, relpath="pkg/comm/coordinator.py")
    assert res.findings == []
    # Hot gather OUTSIDE comm/: not CL012's business.
    res = run_lint(tmp_path, """
        import jax

        def snapshot(params):  # colearn: hot
            return jax.device_get(params)
    """, relpath="pkg/ckpt/mod.py")
    assert res.findings == []


def test_cl012_suppression(tmp_path):
    res = run_lint(tmp_path, """
        import jax
        import numpy as np

        def stage(delta, w):  # colearn: hot
            host = jax.tree.map(np.asarray, delta)  # colearn: noqa(CL012): test fixture
            return scale(host, w)
    """, relpath="pkg/comm/aggregation.py")
    assert res.findings == [] and res.suppressed == 1


def test_cl012_device_fold_region_stays_gather_free(tmp_path):
    # The PR-19 device-fold block (`_fold_block_device`, `# colearn: hot`)
    # retired aggregation.py's last CL012 noqa: staging owns each leaf
    # with a PER-LEAF asarray loop, and the fold itself runs on slots.
    # Pin both directions so the region cannot quietly regress into the
    # full-tree-gather idiom the noqa used to excuse.
    res = run_lint(tmp_path, """
        import jax
        import numpy as np

        def _fold_block_device(self, ids):  # colearn: hot
            leaves, treedef = jax.tree.flatten(self.acc)
            owned = [np.asarray(leaf) * self.w for leaf in leaves]
            return jax.tree.unflatten(treedef, owned)
    """, relpath="pkg/comm/aggregation.py", rules=["CL012"])
    assert res.findings == []
    res = run_lint(tmp_path, """
        import jax
        import numpy as np

        def _fold_block_device(self, ids):  # colearn: hot
            host = jax.tree.map(np.asarray, self.acc)
            return self.kernel.fold(host)
    """, relpath="pkg/comm/aggregation.py", rules=["CL012"])
    assert rule_ids(res) == ["CL012"]


def test_cl013_flags_decompress_in_hot_aggregation_path(tmp_path):
    res = run_lint(tmp_path, """
        from pkg.fed import compression

        def add(self, meta, delta):  # colearn: hot
            dense = compression.decompress_delta(delta, meta,
                                                 shapes=self.shapes)
            return self.stage(dense)
    """, relpath="pkg/comm/aggregation.py", rules=["CL013"])
    assert rule_ids(res) == ["CL013"]
    assert res.exit_code == 1


def test_cl013_flags_full_shape_alloc_in_hot_loop(tmp_path):
    res = run_lint(tmp_path, """
        import numpy as np

        def fold(folder, updates):
            for meta, idx, vals in updates:  # colearn: hot
                buf = np.zeros(folder.model_shape, np.float32)
                buf.reshape(-1)[idx] = vals
                folder.accumulate(buf)
    """, relpath="pkg/comm/aggregation.py", rules=["CL013"])
    assert rule_ids(res) == ["CL013"]


def test_cl013_allows_cold_paths_and_other_dirs(tmp_path):
    # The once-per-round accumulator densify at finalize is NOT hot.
    res = run_lint(tmp_path, """
        import numpy as np

        def finalize(self, staged):
            acc = np.zeros(self.model_shape, np.float32)
            for idx, vals in staged:
                acc.reshape(-1)[idx] += vals
            return acc
    """, relpath="pkg/comm/aggregation.py", rules=["CL013"])
    assert res.findings == []
    # Hot full-shape alloc OUTSIDE comm/: not CL013's business.
    res = run_lint(tmp_path, """
        import numpy as np

        def estimate(shape):  # colearn: hot
            return np.zeros(shape, np.float32)
    """, relpath="pkg/fleetsim/mod.py", rules=["CL013"])
    assert res.findings == []


def test_cl013_suppression(tmp_path):
    # int8 dequantize is inherently dense — the sanctioned noqa shape.
    res = run_lint(tmp_path, """
        from pkg.fed import compression

        def add(self, meta, delta):  # colearn: hot
            dense = compression.decompress_delta(  # colearn: noqa(CL013): test fixture
                delta, meta, shapes=self.shapes)
            return self.stage(dense)
    """, relpath="pkg/comm/aggregation.py", rules=["CL013"])
    assert res.findings == [] and res.suppressed == 1


def test_cl014_flags_raw_clock_delta_in_hot_wire_path(tmp_path):
    res = run_lint(tmp_path, """
        import time

        def collect(self, devs):  # colearn: hot
            t0 = time.perf_counter()
            out = [self.ask(d) for d in devs]
            dt = time.perf_counter() - t0
            print("collected in", dt)
            return out
    """, relpath="pkg/comm/coordinator.py", rules=["CL014"])
    assert rule_ids(res) == ["CL014"]
    assert res.exit_code == 1


def test_cl014_allows_attributed_deltas_and_deadline_math(tmp_path):
    # Accumulation into a named stat (the StreamingFolder.fold_s idiom)
    # is attributed — the delta lands in round meta.
    res = run_lint(tmp_path, """
        import time

        def add(self, meta, delta):  # colearn: hot
            t0 = time.perf_counter()
            self.stage(meta, delta)
            self.fold_s += time.perf_counter() - t0
    """, relpath="pkg/comm/aggregation.py", rules=["CL014"])
    assert res.findings == []
    # A delta fed straight to a registry histogram is attributed.
    res = run_lint(tmp_path, """
        import time

        def fold(self, reg, parts):  # colearn: hot
            t0 = time.monotonic()
            for p in parts:
                self.merge(p)
            reg.histogram("fed.phase_time_s").observe(
                time.monotonic() - t0)
    """, relpath="pkg/comm/aggregator.py", rules=["CL014"])
    assert res.findings == []
    # Deadline arithmetic keeps the clock on the RIGHT — budget
    # bookkeeping, not an unattributed duration.
    res = run_lint(tmp_path, """
        import time

        def wait(self, fut, deadline):  # colearn: hot
            return fut.result(timeout=deadline - time.monotonic())
    """, relpath="pkg/comm/transport.py", rules=["CL014"])
    assert res.findings == []
    # Cold comm path: eval/debug timing is not CL014's business.
    res = run_lint(tmp_path, """
        import time

        def profile(self, devs):
            t0 = time.time()
            self.ping(devs)
            return time.time() - t0
    """, relpath="pkg/comm/coordinator.py", rules=["CL014"])
    assert res.findings == []
    # Hot raw delta OUTSIDE comm/: other planes keep their own idioms.
    res = run_lint(tmp_path, """
        import time

        def step(batch):  # colearn: hot
            t0 = time.perf_counter()
            run(batch)
            return time.perf_counter() - t0
    """, relpath="pkg/fed/mod.py", rules=["CL014"])
    assert res.findings == []


def test_cl014_suppression(tmp_path):
    res = run_lint(tmp_path, """
        import time

        def drain(self, q):  # colearn: hot
            t0 = time.monotonic()
            q.drain()
            lag = time.monotonic() - t0  # colearn: noqa(CL014): test fixture
            return lag
    """, relpath="pkg/comm/worker.py", rules=["CL014"])
    assert res.findings == [] and res.suppressed == 1


def test_cl015_flags_bare_sleep_in_retry_loop(tmp_path):
    res = run_lint(tmp_path, """
        import time

        def request(self, header, retry):
            for attempt in range(retry.max_retries):
                try:
                    return self.ask(header)
                except OSError:
                    time.sleep(retry.delay(attempt))
    """, relpath="pkg/comm/transport.py", rules=["CL015"])
    assert rule_ids(res) == ["CL015"]
    assert res.exit_code == 1


def test_cl015_allows_event_wait_and_one_shot_sleep(tmp_path):
    # The sanctioned idiom: backoff waits on the owner's stop event.
    res = run_lint(tmp_path, """
        def pump(self):
            while not self._stop.is_set():
                if not self.dispatch():
                    self._stop.wait(0.2)
    """, relpath="pkg/comm/worker.py", rules=["CL015"])
    assert res.findings == []
    # A one-shot sleep outside any loop (startup grace) is not a
    # backoff — CL015 only polices loops.
    res = run_lint(tmp_path, """
        import time

        def start(self):
            self.spawn()
            time.sleep(0.1)
    """, relpath="pkg/comm/broker.py", rules=["CL015"])
    assert res.findings == []
    # Sleeps in loops OUTSIDE comm/: other planes (bench scripts,
    # fleetsim clocks) keep their own idioms.
    res = run_lint(tmp_path, """
        import time

        def poll(path):
            while not path.exists():
                time.sleep(0.5)
    """, relpath="pkg/faults/watch.py", rules=["CL015"])
    assert res.findings == []


def test_cl015_suppression(tmp_path):
    res = run_lint(tmp_path, """
        import time

        def settle(self):
            for _ in range(3):
                time.sleep(0.01)  # colearn: noqa(CL015): test fixture
    """, relpath="pkg/comm/transport.py", rules=["CL015"])
    assert res.findings == [] and res.suppressed == 1


def test_cl016_flags_uncataloged_record_key(tmp_path):
    res = run_lint(tmp_path, """
        def _round(self, r):
            rec = {"round": r, "completed": 3}
            rec["train_los"] = 1.0
            return rec
    """, relpath="pkg/comm/coordinator.py", rules=["CL016"])
    assert rule_ids(res) == ["CL016"]
    assert res.exit_code == 1
    assert "train_los" in res.findings[0].message


def test_cl016_flags_typo_in_dict_literal_and_update(tmp_path):
    # Dict-literal assignment to a record name and .update kwargs are
    # both validated against the catalog.
    res = run_lint(tmp_path, """
        def _round(self, r):
            rec = {"round": r, "cohrt": 4}
            rec.update(train_loss=0.5, stalenes_mean=1.0)
            return rec
    """, relpath="pkg/fleetsim/sim.py", rules=["CL016"])
    assert rule_ids(res) == ["CL016"]
    assert len(res.findings) == 2
    flagged = {f.message.split("'")[1] for f in res.findings}
    assert flagged == {"cohrt", "stalenes_mean"}


def test_cl016_allows_cataloged_keys_and_dynamic_updates(tmp_path):
    # Cataloged keys pass; **splat and computed updates are out of
    # scope (their keys are cataloged at the call sites that build them).
    res = run_lint(tmp_path, """
        def _round(self, r, extras):
            rec = {"round": r, "completed": 3, "train_loss": 0.1}
            rec["conv_update_norm"] = 0.5
            rec.update(**extras)
            rec.update({"staleness_mean": 1.0})
            return rec
    """, relpath="pkg/comm/async_coordinator.py", rules=["CL016"])
    assert res.findings == []
    # Wire-header dicts in other comm/ files keep their own vocabulary.
    res = run_lint(tmp_path, """
        def reply(self):
            out = {"op": "subscribe_ack", "status": "ok"}
            return out
    """, relpath="pkg/comm/broker.py", rules=["CL016"])
    assert res.findings == []


def test_cl016_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def _round(self, r):
            rec = {"round": r}
            rec["experimental_key"] = 1  # colearn: noqa(CL016): test fixture
            return rec
    """, relpath="pkg/comm/coordinator.py", rules=["CL016"])
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------------------------- CL023 ----
def test_cl023_flags_replace_without_fsync_in_ckpt(tmp_path):
    # os.replace alone satisfies CL008's torn-reader contract but not
    # CL023's power-loss one: the rename can land before the data blocks.
    res = run_lint(tmp_path, """
        import os

        def commit(path, body):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(body)
            os.replace(tmp, path)
    """, relpath="pkg/ckpt/gen.py", rules=["CL023"])
    assert rule_ids(res) == ["CL023"]


def test_cl023_flags_in_place_npz_in_offline(tmp_path):
    res = run_lint(tmp_path, """
        import numpy as np

        def export(path, arrays):
            np.savez(path, **arrays)
    """, relpath="pkg/fed/offline.py", rules=["CL023"])
    assert rule_ids(res) == ["CL023"]


def test_cl023_passes_fsync_before_replace_and_atomic_helper(tmp_path):
    res = run_lint(tmp_path, """
        import os
        import numpy as np
        from pkg.utils.serialization import atomic_save_pytree_npz

        def commit(path, body):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(body)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)

        def shard_write(path, buffers):
            _atomic_write(path, lambda f: np.savez(f, **buffers))

        def export(path, tree):
            atomic_save_pytree_npz(path, tree)
    """, relpath="pkg/ckpt/streaming.py", rules=["CL023"])
    assert res.findings == []


def test_cl023_only_applies_to_durable_paths(tmp_path):
    # The same in-place write outside ckpt/ and fed/offline.py is CL008's
    # (or nobody's) business, not CL023's.
    res = run_lint(tmp_path, """
        def scratch(path, body):
            with open(path, "w") as f:
                f.write(body)
    """, relpath="pkg/comm/mod.py", rules=["CL023"])
    assert res.findings == []


def test_cl023_suppression(tmp_path):
    res = run_lint(tmp_path, """
        def scratch(path, body):
            with open(path, "w") as f:  # colearn: noqa(CL023): test fixture
                f.write(body)
    """, relpath="pkg/ckpt/tmp.py", rules=["CL023"])
    assert res.findings == [] and res.suppressed == 1


# ------------------------------------------- engine machinery ----------
def test_cl000_dead_suppression_is_reported(tmp_path):
    res = run_lint(tmp_path, """
        X = 1  # colearn: noqa(CL002)
    """)
    assert rule_ids(res) == ["CL000"]


def test_blanket_noqa_suppresses_every_rule_on_the_line(tmp_path):
    res = run_lint(tmp_path, """
        import time

        def jitter():
            return time.time()  # colearn: noqa
    """, relpath="pkg/faults/mod.py")
    assert res.findings == [] and res.suppressed == 1


def test_syntax_error_becomes_cl999_finding(tmp_path):
    res = run_lint(tmp_path, "def broken(:\n")
    assert rule_ids(res) == ["CL999"]
    assert res.exit_code == 1


def test_docstring_mentioning_noqa_does_not_suppress(tmp_path):
    res = run_lint(tmp_path, '''
        def teardown(sock):
            """Mentions # colearn: noqa(CL003) in prose only."""
            try:
                sock.close()
            except OSError:
                pass
    ''')
    assert rule_ids(res) == ["CL003"]


def test_baseline_absorbs_findings_and_survives_line_shifts(tmp_path):
    src = """
        def teardown(sock):
            try:
                sock.close()
            except OSError:
                pass
    """
    res = run_lint(tmp_path, src)
    assert len(res.findings) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), res.findings)

    # Same finding, two lines lower: the fingerprint hashes source text,
    # not line numbers, so the baseline still covers it.
    shifted = "\n# shifted\n# shifted\n" + textwrap.dedent(src)
    res2 = run_lint(tmp_path, shifted, baseline=str(bl))
    assert res2.findings == [] and res2.baselined == 1


def test_unknown_rule_id_raises():
    with pytest.raises(ValueError, match="unknown lint rule"):
        LintEngine(config=LintConfig(enable=["CL404"]))


def test_config_disable_skips_rule(tmp_path):
    res = run_lint(tmp_path, """
        def drain(sock):
            return sock.recv(4)
    """, rules=None, baseline="")
    assert rule_ids(res) == ["CL002"]
    path = tmp_path / "pkg/comm/mod.py"
    eng = LintEngine(config=LintConfig(disable=("CL002",)),
                     root=str(tmp_path))
    assert eng.run([str(path)], baseline_path="").findings == []


# ------------------------------------------------------------- CLI ------
def _write_fixture(tmp_path, source, relpath="pkg/comm/mod.py"):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return path


def test_cli_lint_exits_nonzero_and_emits_json(tmp_path, capsys):
    bad = _write_fixture(tmp_path, """
        def drain(sock):
            return sock.recv(4)
    """)
    rc = cli_main(["lint", str(bad), "--root", str(tmp_path),
                   "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["counts"] == {"CL002": 1}
    assert doc["findings"][0]["rule"] == "CL002"
    assert doc["findings"][0]["line"] == 3


def test_cli_lint_exits_zero_on_clean_tree(tmp_path, capsys):
    clean = _write_fixture(tmp_path, "X = 1\n")
    rc = cli_main(["lint", str(clean), "--root", str(tmp_path)])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


def test_cli_lint_unknown_rule_is_usage_error(tmp_path, capsys):
    clean = _write_fixture(tmp_path, "X = 1\n")
    rc = cli_main(["lint", str(clean), "--root", str(tmp_path),
                   "--rules", "CL404"])
    capsys.readouterr()
    assert rc == 2


def test_cli_write_baseline_then_clean(tmp_path, capsys):
    _write_fixture(tmp_path, """
        def drain(sock):
            return sock.recv(4)
    """)
    target = str(tmp_path / "pkg")
    rc = cli_main(["lint", target, "--root", str(tmp_path),
                   "--write-baseline"])
    capsys.readouterr()
    assert rc == 0
    assert (tmp_path / "lint_baseline.json").exists()
    rc = cli_main(["lint", target, "--root", str(tmp_path)])
    capsys.readouterr()
    assert rc == 0


# ------------------------------------------- labeled counters ----------
def test_counter_labels_roll_up_into_aggregate():
    reg = MetricsRegistry()
    reg.counter("comm.retry_total", labels={"device": "3"}).inc(2)
    reg.counter("comm.retry_total", labels={"device": "5"}).inc()
    snap = reg.snapshot()
    assert snap["comm.retry_total"] == 3.0
    assert snap["comm.retry_total{device=3}"] == 2.0
    assert snap["comm.retry_total{device=5}"] == 1.0


def test_counter_labels_same_set_returns_same_child():
    reg = MetricsRegistry()
    a = reg.counter("comm.retry_total", labels={"device": "3"})
    b = reg.counter("comm.retry_total", labels={"device": "3"})
    assert a is b
    # The unlabeled aggregate is the parent, untouched until a child incs.
    assert reg.counter("comm.retry_total").value == 0.0


def test_strict_mode_rejects_uncataloged_names(monkeypatch):
    monkeypatch.setattr(telemetry_registry, "_STRICT", True)
    reg = MetricsRegistry()
    reg.counter("comm.retry_total").inc()           # cataloged: fine
    reg.counter("fault.injected.delay")             # wildcard family: fine
    with pytest.raises(ValueError, match="metric_catalog"):
        reg.counter("comm.retry_totl")


# ------------------------------------------- tier-1 self-check ----------
def test_installed_package_is_lint_clean():
    import colearn_federated_learning_tpu as pkg

    pkg_dir = os.path.dirname(os.path.abspath(pkg.__file__))
    root = os.path.dirname(pkg_dir)
    eng = LintEngine(config=LintConfig.from_pyproject(root), root=root)
    res = eng.run([pkg_dir])
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    assert res.files > 50
