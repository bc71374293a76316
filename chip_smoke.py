"""Chip smoke: ``colearn train`` on the accelerator, through ``cli.main``.

    python chip_smoke.py

One process, no children, no CPU mode.  Three phases, each one ordinary
``train`` command at the model's full width and depth with seeded random
weights and the registry's synthetic data:

1. ``cifar10_cnn_fedavg`` — width-64 bf16 CNN, 100 Dirichlet clients,
   cohort 20, 3 rounds;
2. ``agnews_bert_fedavg`` — BERT-base (768 x 12 layers x 12 heads, seq
   128, vocab 30,522, bf16 compute, Adam clients), 50 clients, batch 16,
   4 local steps, 3 rounds — at cohort 9, the largest that fits one v5e:
   the config's cohort 10 needs 15.96 GB of the chip's 15.75 GB (float32
   weights, gradients and two Adam moments per vmapped client) and XLA
   refuses it at compile time;
3. the same BERT with ``--attn-impl flash``, 2 rounds, so that a Pallas
   kernel the compiler refuses fails the run.

A phase fails unless the summary names the TPU, every ``train_loss`` is
finite, the last round carries ``eval_loss``/``eval_acc``, the round
program was compiled once and never again, ``hbm_used_gb`` is in every
round record, the flash phase traced compiled (not interpreted)
kernels, and its round-0 loss agrees with the dense phase's.  The last
line of stdout is ``{"ok": true, "device": {...}}``; any failure raises
and the process exits non-zero without printing it.

The phase functions take the ``train`` arguments as a list so that
tests/test_chip_smoke.py can drive them on the CPU at a tiny size.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.metadata
import io
import json
import math
import os
import sys
import tempfile

BERT = ["--config", "agnews_bert_fedavg", "--local-steps", "4",
        "--cohort-size", "9"]
PHASES = (
    ("cnn", ["--config", "cifar10_cnn_fedavg", "--rounds", "3"]),
    ("bert_dense", [*BERT, "--rounds", "3"]),
    ("bert_flash", [*BERT, "--rounds", "2", "--attn-impl", "flash"]),
)

# Round-0 train_loss, flash against dense, same seed (same init, cohort
# and batches).  The two cores differ only in the order of the softmax
# arithmetic, fed by bf16 activations (8 mantissa bits, 2^-8 = 3.9e-3
# relative per rounding); the loss is a mean over cohort x steps x batch
# = 576 examples of a 12-layer forward, so per-element roundings largely
# average out.  2^-8 — one bf16 ulp of the loss itself — holds with
# margin: first contact on a v5e measured a relative gap of 2.0e-4.
FLASH_VS_DENSE_RTOL = 2.0 ** -8


class SmokeFailure(RuntimeError):
    """A phase ran to the end but what came out is wrong."""


@contextlib.contextmanager
def _watch_compiles():
    """jax's own compile events over a window: seconds spent in backend
    compilation (a persistent-cache hit counts its load time) and the
    persistent cache's hit/miss tally."""
    from jax import monitoring

    seen = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compile_s"] += duration

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        monitoring.unregister_event_listener(on_event)
        monitoring.unregister_event_duration_listener(on_duration)


def _counters() -> dict:
    from colearn_federated_learning_tpu import telemetry

    reg = telemetry.get_registry()
    return {
        "round_compiles": reg.counter(
            "telemetry.compile_total", labels={"fn": "engine.round"}).value,
        "flash_mosaic": reg.counter(
            "ops.flash_trace_total", labels={"mode": "mosaic"}).value,
        "flash_interpret": reg.counter(
            "ops.flash_trace_total", labels={"mode": "interpret"}).value,
    }


def run_train(train_argv: list[str]) -> dict:
    """One ``colearn train`` through ``cli.main``, in this process.
    Returns its summary line, its round records (read back from the
    ``--log-file`` a user would pass) and what the process counted while
    it ran."""
    from colearn_federated_learning_tpu import cli, telemetry

    before = _counters()
    stdout = io.StringIO()
    with tempfile.TemporaryDirectory() as tmp:
        log = os.path.join(tmp, "rounds.jsonl")
        with _watch_compiles() as compiles, contextlib.redirect_stdout(stdout):
            rc = cli.main(["train", *train_argv, "--log-file", log])
        if rc != 0:
            raise SmokeFailure(f"train {train_argv} returned {rc}")
        with open(log) as f:
            records = [json.loads(line) for line in f]
    after = _counters()
    # The learner died with cmd_train; make sure its device buffers are
    # gone before the next phase sizes itself against the same HBM.
    gc.collect()
    return {
        "summary": json.loads(stdout.getvalue().strip().splitlines()[-1]),
        "records": records,
        **{k: int(after[k] - before[k]) for k in after},
        **compiles,
        "memory_stats": telemetry.sample_device_memory(),
    }


def check_phase(name: str, run: dict, platform: str) -> dict:
    """Raise :class:`SmokeFailure` unless ``run`` is a healthy training
    run on ``platform``; return the phase's one-line report."""
    summary, records = run["summary"], run["records"]

    def fail(why: str):
        raise SmokeFailure(f"phase {name}: {why}")

    if summary.get("platform") != platform:
        fail(f"ran on {summary.get('platform')!r}, not {platform!r}")
    if len(records) != summary["rounds"] or not records:
        fail(f"{len(records)} round records for {summary['rounds']} rounds")
    for rec in records:
        if not math.isfinite(rec["train_loss"]):
            fail(f"round {rec['round']} train_loss {rec['train_loss']}")
        if "recompiles" in rec:
            fail(f"round {rec['round']} recompiled ({rec['recompiles']})")
        # memory_stats() answers on the chip and not on the CPU; a chip
        # that stops answering must not pass for a CPU.
        if platform != "cpu" and "hbm_used_gb" not in rec:
            fail(f"round {rec['round']} has no hbm_used_gb")
    last = records[-1]
    for key in ("eval_loss", "eval_acc"):
        if not math.isfinite(last.get(key, math.nan)):
            fail(f"last round {key} is {last.get(key)!r}")
    if run["round_compiles"] != 1:
        fail(f"{run['round_compiles']} round-program signatures, want 1")
    steady = [r["round_time_s"] for r in records[1:]]
    return {
        "phase": name,
        "platform": summary["platform"],
        "device_kind": summary["device_kind"],
        "n_chips": summary["n_chips"],
        "rounds": len(records),
        "train_loss": [round(r["train_loss"], 6) for r in records],
        "eval_loss": last["eval_loss"],
        "eval_acc": last["eval_acc"],
        "compile_s": round(run["compile_s"], 2),
        "cache_hits": run["cache_hits"],
        "cache_misses": run["cache_misses"],
        "steady_rounds_per_sec": (
            round(len(steady) / sum(steady), 4) if steady else None),
        "hbm_used_gb": last.get("hbm_used_gb"),
        # Process-wide high-water marks so far.  On this runtime
        # peak_bytes_in_use counts live buffers only; the round program's
        # own temporaries show in peak_bytes_reserved.
        **{k: run["memory_stats"].get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")},
    }


def check_flash(dense: dict, flash: dict, interpret: bool) -> float:
    """The flash phase must have traced kernels of the expected lowering
    only, and start from the dense phase's loss.  Returns the relative
    round-0 gap."""
    want, other = (("flash_interpret", "flash_mosaic") if interpret
                   else ("flash_mosaic", "flash_interpret"))
    if flash[want] < 1 or flash[other] != 0:
        raise SmokeFailure(
            f"flash phase traced {flash['flash_mosaic']} compiled and "
            f"{flash['flash_interpret']} interpreted kernels")
    if dense["flash_mosaic"] or dense["flash_interpret"]:
        raise SmokeFailure("dense phase traced a flash kernel")
    d = dense["records"][0]["train_loss"]
    f = flash["records"][0]["train_loss"]
    gap = abs(f - d) / abs(d)
    if not gap <= FLASH_VS_DENSE_RTOL:
        raise SmokeFailure(
            f"round-0 train_loss flash {f} vs dense {d}: relative gap "
            f"{gap:.3e} > {FLASH_VS_DENSE_RTOL:.3e}")
    return gap


def main() -> int:
    from colearn_federated_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU, jax.devices() = {devices}",
              file=sys.stderr)
        return 1
    print(json.dumps({
        "device_kind": devices[0].device_kind, "count": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache_dir": cache_dir,
    }), flush=True)

    runs, reports = {}, {}
    for name, argv in PHASES:
        runs[name] = run_train([*argv, "--backend", "tpu"])
        reports[name] = check_phase(name, runs[name], "tpu")
        print(json.dumps(reports[name]), flush=True)
    gap = check_flash(runs["bert_dense"], runs["bert_flash"],
                      interpret=False)
    print(json.dumps({"flash_vs_dense_round0_rel_gap": gap,
                      "rtol": FLASH_VS_DENSE_RTOL}), flush=True)

    # The device of the result line is what the summaries named; it must
    # be one device set, and all of what jax reported at the start.
    named = {(r["platform"], r["device_kind"], r["n_chips"])
             for r in reports.values()}
    if named != {("tpu", devices[0].device_kind, len(devices))}:
        raise SmokeFailure(
            f"phases trained on {sorted(named)}, jax has {devices}")
    platform, kind, count = named.pop()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
