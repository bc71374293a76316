"""XLA/JAX runtime introspection + live metric export.

The span tracer answers *where a round spent its time*; this module
answers the production questions the spans cannot:

- **Is the program recompiling?**  :class:`CompileTracker` wraps a
  jitted callable and fingerprints every call's abstract signature
  (treedef + leaf shape/dtype).  The first distinct signature is the
  expected compile (``telemetry.compile_total{fn=...}``); every later
  NEW signature is a recompile, counted with an attributed reason —
  ``telemetry.recompile_total{fn=...,reason=shape|dtype|structure}`` —
  and so is a call that repeats a signature yet grows the jitted
  function's own executable cache (``reason=placement``: the arguments
  moved to another sharding or device), so "the coordinator silently
  recompiles every round" is a visible counter, and fleetsim's
  one-compile-per-sweep claim is a tested invariant instead of a
  docstring.  A call that adds an executable also adds the seconds it
  blocked to ``telemetry.compile_seconds{fn=...}``, and while a tracked
  call runs, jax's own persistent-cache events on that thread count as
  ``telemetry.cache_hit_total{fn=...}`` (the program was loaded) or
  ``telemetry.cache_miss_total{fn=...}`` (it was built and written), so
  set-up time has a name: which program, built or loaded, how long.
  :func:`tracked_call` gives the same attribution to a block that
  compiles eagerly (``engine.from_config``).
- **What does one round cost?**  :func:`compiled_cost` runs XLA's own
  ``cost_analysis`` on the AOT-compiled executable (cached per
  signature, so asking twice is free) — the automated replacement for
  the manual lower/compile procedure PERF.md used to prescribe.
- **Is HBM creeping toward OOM?**  :func:`sample_device_memory` turns
  ``device.memory_stats()`` into live gauges
  (``runtime.hbm_bytes_in_use`` / ``..._limit`` / ``..._peak``).
- **How do I watch it?**  :func:`prometheus_text` renders a registry
  snapshot in Prometheus text exposition format; :class:`MetricsExporter`
  serves it from a stdlib HTTP thread (``/metrics``, plus the raw JSON
  snapshot at ``/snapshot.json`` that ``colearn top`` consumes); and
  :class:`EventLog` appends machine-readable JSONL events (round
  records, lifecycle marks) for the push-based half.

Everything here is dependency-free host-side code: no prometheus
client, no agent, no thread unless an exporter is explicitly started.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import re
import threading
import time
from typing import Optional

from colearn_federated_learning_tpu.telemetry.registry import (
    MetricsRegistry,
    get_registry,
)

__all__ = [
    "CompileTracker",
    "EventLog",
    "MetricsExporter",
    "compiled_cost",
    "prometheus_text",
    "sample_device_memory",
    "tracked_call",
]


# ------------------------------------------------------------ signatures --
def _leaf_abstract(leaf) -> tuple:
    """(shape, dtype) for array-likes; (type-name, value-ignored) for
    host scalars — a Python int changing VALUE must not read as a
    recompile (weak-typed scalars usually re-trace only on type)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    return ((), type(leaf).__name__)


def abstract_signature(args: tuple, kwargs: dict) -> tuple:
    """Hashable abstraction of a call: (treedef repr, leaf abstracts).
    Two calls with the same signature hit the same jit-cache entry;
    a differing signature is (at least) a cache miss."""
    import jax

    leaves, treedef = jax.tree.flatten((args, kwargs))
    return (str(treedef), tuple(_leaf_abstract(l) for l in leaves))


def _recompile_reason(prev_sigs, sig) -> str:
    """Attribute WHY a new signature missed the cache, against the most
    recently seen signature: structure (treedef) > dtype > shape."""
    if not prev_sigs:
        return "shape"
    treedef, leaves = sig
    p_treedef, p_leaves = prev_sigs[-1]
    if treedef != p_treedef or len(leaves) != len(p_leaves):
        return "structure"
    if any(l[1] != p[1] for l, p in zip(leaves, p_leaves)):
        return "dtype"
    return "shape"


# ------------------------------------------------- cache hits and misses --
# jax reports its persistent compilation cache through process-wide
# monitoring events that say nothing of which program they concern.  The
# tracked call in progress on the thread that compiles says it.
_tracked = threading.local()


def _on_cache_event(event: str, **_) -> None:
    call = getattr(_tracked, "call", None)
    if call is None:
        return                      # not in a call of the program
    fn, registry = call
    reg = registry if registry is not None else get_registry()
    if event == "/jax/compilation_cache/cache_hits":
        reg.counter("telemetry.cache_hit_total", labels={"fn": fn}).inc()
    elif event == "/jax/compilation_cache/cache_misses":
        reg.counter("telemetry.cache_miss_total", labels={"fn": fn}).inc()


@functools.cache
def _listen_for_cache_events() -> None:
    """One listener for the life of the process, registered by the first
    tracked call."""
    from jax import monitoring

    monitoring.register_event_listener(_on_cache_event)


@contextlib.contextmanager
def tracked_call(fn: str, registry: Optional[MetricsRegistry] = None):
    """While the block runs on this thread, jax's persistent-cache hits and
    misses are counted under ``fn``.  Events outside any such block (a
    caller's own programs) are not this program's and are not counted."""
    _listen_for_cache_events()
    previous = getattr(_tracked, "call", None)
    _tracked.call = (fn, registry)
    try:
        yield
    finally:
        _tracked.call = previous


class CompileTracker:
    """Transparent wrapper around a (jitted) callable that counts the
    distinct call signatures it has seen.

    ``tracker(...)`` forwards to the wrapped fn; attribute access
    (``.lower``, ``.trace`` …) passes through, so code holding the
    tracker can keep using the jit AOT surface.  ``compiles`` is the
    number of distinct signatures, plus every executable jit built for
    a signature it had already seen — the executable count a correct
    static-shape pipeline holds at exactly 1 per sweep shape.
    """

    def __init__(self, fn, name: str,
                 registry: Optional[MetricsRegistry] = None):
        self._fn = fn
        self.name = name
        self._registry = registry
        self._sigs: list = []
        self._sig_set: set = set()
        self._jit_entries = 0
        self._placement_recompiles = 0
        self._cost_cache: dict = {}
        self._lock = threading.Lock()

    # -- introspection --------------------------------------------------
    @property
    def compiles(self) -> int:
        return len(self._sigs) + self._placement_recompiles

    @property
    def recompiles(self) -> int:
        return max(0, self.compiles - 1)

    def _reg(self) -> MetricsRegistry:
        return self._registry if self._registry is not None else (
            get_registry())

    def _note(self, sig) -> bool:
        """Count the call just made; True if it added an executable."""
        # A jitted fn counts its own executables; shape/dtype/structure
        # are not all it keys them on.
        cache_size = getattr(self._fn, "_cache_size", None)
        entries = cache_size() if cache_size is not None else 0
        with self._lock:
            grew = entries > self._jit_entries
            self._jit_entries = max(entries, self._jit_entries)
            if sig in self._sig_set:
                if not grew:
                    return False
                reason = "placement"
                self._placement_recompiles += 1
            else:
                reason = None
                if self._sigs:
                    reason = _recompile_reason(self._sigs, sig)
                self._sig_set.add(sig)
                self._sigs.append(sig)
        reg = self._reg()
        reg.counter("telemetry.compile_total",
                    labels={"fn": self.name}).inc()
        if reason is not None:
            reg.counter("telemetry.recompile_total",
                        labels={"fn": self.name, "reason": reason}).inc()
        return True

    # -- call surface ---------------------------------------------------
    def __call__(self, *args, **kwargs):
        sig = abstract_signature(args, kwargs)
        with tracked_call(self.name, self._registry):
            t0 = time.perf_counter()
            out = self._fn(*args, **kwargs)
            blocked_s = time.perf_counter() - t0
        if self._note(sig):
            # Tracing, building or loading, and the enqueue: what the
            # caller waited for this executable.
            self._reg().counter("telemetry.compile_seconds",
                                labels={"fn": self.name}).inc(blocked_s)
        return out

    def __getattr__(self, attr):
        return getattr(self._fn, attr)

    # -- cost analysis --------------------------------------------------
    def cost_analysis(self, *args, **kwargs) -> dict:
        """XLA ``cost_analysis`` of the executable for THIS signature
        (AOT lower+compile; cached per signature so repeated asks are
        free).  Returns ``{}`` when the wrapped fn has no ``lower``."""
        sig = abstract_signature(args, kwargs)
        with self._lock:
            cached = self._cost_cache.get(sig)
        if cached is not None:
            return dict(cached)
        cost = compiled_cost(self._fn, *args, **kwargs)
        with self._lock:
            self._cost_cache[sig] = cost
        return dict(cost)


def compiled_cost(fn, *args, **kwargs) -> dict:
    """Lower + AOT-compile ``fn`` for these operands and return XLA's
    ``cost_analysis`` dict plus ``compile_s``.  ``{}``-valued keys when
    the backend reports nothing (CPU often does).  NOTE: XLA counts a
    while/scan body ONCE — callers whose FLOPs live in a scan must scale
    by the trip count themselves."""
    if not hasattr(fn, "lower"):
        return {}
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kwargs).compile()
    compile_s = time.perf_counter() - t0
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
    out = {k: float(v) for k, v in cost.items()
           if isinstance(v, (int, float))}
    out["compile_s"] = compile_s
    return out


# ------------------------------------------------------------ HBM gauges --
def sample_device_memory(
        registry: Optional[MetricsRegistry] = None) -> dict:
    """Sample ``device.memory_stats()`` of the first local device into
    live gauges; returns the raw stats dict (``{}`` when the backend —
    CPU, typically — reports none).  Cheap host call, safe every round."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except (RuntimeError, IndexError, NotImplementedError):
        stats = {}
    if stats:
        reg = registry if registry is not None else get_registry()
        if "bytes_in_use" in stats:
            reg.gauge("runtime.hbm_bytes_in_use").set(
                stats["bytes_in_use"])
        if "bytes_limit" in stats:
            reg.gauge("runtime.hbm_bytes_limit").set(stats["bytes_limit"])
        if "peak_bytes_in_use" in stats:
            reg.gauge("runtime.hbm_peak_bytes_in_use").set(
                stats["peak_bytes_in_use"])
    return stats


# -------------------------------------------------------- Prometheus text --
_LABELED_RE = re.compile(r"^(?P<base>[^{]+)\{(?P<labels>.*)\}$")
_INVALID_CHARS = re.compile(r"[^a-zA-Z0-9_]")


def _prom_name(name: str) -> str:
    return "colearn_" + _INVALID_CHARS.sub("_", name)


def _prom_labels(label_str: str) -> str:
    pairs = []
    for item in label_str.split(","):
        if not item:
            continue
        k, _, v = item.partition("=")
        v = v.replace("\\", "\\\\").replace('"', '\\"')
        pairs.append(f'{k}="{v}"')
    return "{" + ",".join(pairs) + "}"


def prometheus_text(typed_snapshot: dict) -> str:
    """Render a :meth:`MetricsRegistry.typed_snapshot` in the Prometheus
    text exposition format (version 0.0.4).

    Counters/gauges become single samples; histograms become Prometheus
    summaries (``_count``/``_sum`` + ``{quantile=...}`` lines).  Labeled
    children (``name{k=v}``) share their parent's metric family.  Gauges
    never set stay out of the exposition entirely.
    """
    families: dict = {}
    for name, (kind, value) in sorted(typed_snapshot.items()):
        m = _LABELED_RE.match(name)
        base, labels = (m.group("base"), m.group("labels")) if m else (
            name, None)
        families.setdefault(base, {"kind": kind, "samples": []})
        families[base]["samples"].append((labels, value))
    lines = []
    for base in sorted(families):
        kind = families[base]["kind"]
        pname = _prom_name(base)
        if kind == "histogram":
            lines.append(f"# TYPE {pname} summary")
            for labels, summary in families[base]["samples"]:
                # A labeled child merges its labels into each quantile
                # line and suffixes _count/_sum, sharing the family of
                # the unlabeled aggregate parent.
                extra = ""
                if labels is not None:
                    extra = _prom_labels(labels)[1:-1]  # inner k="v" pairs
                for q, key in (("0.5", "p50"), ("0.9", "p90"),
                               ("0.99", "p99")):
                    if summary.get(key) is not None:
                        qlabels = f'quantile="{q}"' + (
                            f",{extra}" if extra else "")
                        lines.append(
                            f'{pname}{{{qlabels}}} '
                            f'{summary[key]:.10g}')
                suffix = "{" + extra + "}" if extra else ""
                lines.append(f"{pname}_count{suffix} {summary['count']}")
                lines.append(
                    f"{pname}_sum{suffix} {summary['sum']:.10g}")
            continue
        samples = [(labels, value)
                   for labels, value in families[base]["samples"]
                   if value is not None]    # gauges never set are skipped
        if not samples:
            continue                  # no samples, no family header
        lines.append(f"# TYPE {pname} {kind}")
        for labels, value in samples:
            suffix = _prom_labels(labels) if labels is not None else ""
            lines.append(f"{pname}{suffix} {float(value):.10g}")
    return "\n".join(lines) + "\n"


# ------------------------------------------------------------- exporter --
class MetricsExporter:
    """Pull-based exporter: a daemon HTTP thread serving the process
    registry.  ``GET /metrics`` → Prometheus text; ``GET /snapshot.json``
    → the raw registry snapshot (what ``colearn top`` renders).

    ``port=0`` binds an ephemeral port (read it back from ``.port`` —
    the CLI announces it on stderr so harnesses can find it).
    """

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 registry: Optional[MetricsRegistry] = None):
        self._registry = registry
        self._host = host
        self._want_port = port
        self._server = None
        self._thread = None

    def _reg(self) -> MetricsRegistry:
        return self._registry if self._registry is not None else (
            get_registry())

    @property
    def port(self) -> Optional[int]:
        return self._server.server_address[1] if self._server else None

    def start(self) -> "MetricsExporter":
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        exporter = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):          # noqa: N802  (stdlib handler name)
                reg = exporter._reg()
                if self.path.startswith("/metrics"):
                    body = prometheus_text(reg.typed_snapshot()).encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif self.path.startswith("/snapshot.json"):
                    body = json.dumps(reg.snapshot()).encode()
                    ctype = "application/json"
                else:
                    self.send_error(404)
                    return
                reg.counter("export.scrapes_total").inc()
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, fmt, *log_args):
                pass                   # scrapes must not spam stderr

        self._server = ThreadingHTTPServer((self._host, self._want_port),
                                           Handler)
        self._server.daemon_threads = True
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="metrics-exporter",
            daemon=True)
        self._thread.start()
        return self

    def close(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
            self._thread = None

    def __enter__(self):
        return self.start() if self._server is None else self

    def __exit__(self, *exc):
        self.close()


# -------------------------------------------------------------- EventLog --
class EventLog:
    """Push-based JSONL event stream: one JSON object per line, flushed
    per write so a tail (or a post-crash reader) always sees complete
    recent events.  Events carry ``ts`` (epoch) and ``event`` (type)."""

    def __init__(self, path: str):
        self.path = path
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self._lock = threading.Lock()

    def emit(self, event: str, **payload) -> None:
        doc = {"ts": time.time(), "event": event, **payload}
        line = json.dumps(doc, separators=(",", ":"), default=str) + "\n"
        with self._lock:
            if self._f is None:
                return
            self._f.write(line)
            self._f.flush()
        self._reg_count()

    def _reg_count(self) -> None:
        get_registry().counter("export.events_written_total").inc()

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


# ---------------------------------------------------------- `colearn top` --
def render_top(snapshot: dict, prev: Optional[dict] = None,
               interval_s: float = 0.0) -> str:
    """Terminal dashboard body from a registry snapshot (pure function —
    the CLI loops it; tests call it directly).  ``prev`` + ``interval_s``
    turn cumulative counters into per-second rates."""

    def val(name, default=0.0):
        v = snapshot.get(name)
        return default if v is None or isinstance(v, dict) else float(v)

    def rate(name):
        if not prev or interval_s <= 0:
            return None
        return (val(name) - float(prev.get(name) or 0.0)) / interval_s

    lines = ["colearn top — live federation metrics", ""]
    rounds = (val("fed.rounds_total") or val("engine.rounds_total")
              or val("fleetsim.rounds_total"))
    rps = (rate("fed.rounds_total") or rate("engine.rounds_total")
           or rate("fleetsim.rounds_total"))
    lines.append(f"rounds total        {rounds:>12.0f}"
                 + (f"   ({rps:.3f}/s)" if rps is not None else ""))
    rt = snapshot.get("fed.round_time_s") or snapshot.get(
        "engine.round_time_s") or snapshot.get("fleetsim.round_time_s")
    if isinstance(rt, dict) and rt.get("count"):
        lines.append(
            f"round time          p50 {rt.get('p50', 0.0):.3f}s   "
            f"p90 {rt.get('p90', 0.0):.3f}s   max {rt.get('max', 0.0):.3f}s")
    lines.append("")
    lines.append("cohort health")
    for label, name in (("  clients dropped  ", "fed.clients_dropped"),
                        ("  clients evicted  ", "fed.clients_evicted"),
                        ("  quorum skips     ", "fed.rounds_skipped_quorum"),
                        ("  resumes          ", "fed.rounds_resumed_total")):
        lines.append(f"{label}{val(name):>12.0f}")
    lines.append("")
    lines.append("faults / retries")
    for label, name in (("  retries          ", "comm.retry_total"),
                        ("  corrupt frames   ", "comm.corrupt_frames_total"),
                        ("  faults injected  ", "fault.injected_total"),
                        ("  reconnect fails  ",
                         "comm.reconnect_failures_total")):
        lines.append(f"{label}{val(name):>12.0f}")
    # Aggregator tier: shown only when a tree is (or was) enrolled —
    # per-agg rows come from the coordinator-side labeled children
    # (heartbeat age gauge, slice-size gauge, partials-folded counter).
    agg_rows: dict[str, dict] = {}
    for name, v in snapshot.items():
        m = _LABELED_RE.match(name)
        if not m or v is None or isinstance(v, dict):
            continue
        base, labels = m.group("base"), m.group("labels")
        field = {"comm.agg_heartbeat_age_s": "hb_age",
                 "comm.agg_slice_devices": "slice",
                 "comm.agg_partials_folded_total": "partials"}.get(base)
        if field is None:
            continue
        agg = dict(item.partition("=")[::2] for item in labels.split(","))
        agg_id = agg.get("agg")
        if agg_id is None:
            continue
        agg_rows.setdefault(agg_id, {})[field] = float(v)
    failovers = val("comm.agg_failovers_total")
    expired = val("comm.agg_heartbeat_expired_total")
    if agg_rows or failovers or expired:
        lines.append("")
        lines.append("aggregator tier")
        for agg_id in sorted(agg_rows):
            row = agg_rows[agg_id]
            lines.append(
                f"  agg {agg_id:<4} hb age {row.get('hb_age', 0.0):>7.2f}s"
                f"   slice {row.get('slice', 0.0):>4.0f}"
                f"   partials {row.get('partials', 0.0):>6.0f}")
        lines.append(f"  failovers        {failovers:>12.0f}")
        lines.append(f"  heartbeats expired{expired:>11.0f}")
    # Async plane (the staleness observatory): shown only when the
    # buffered-async coordinator — or fleetsim's async mode — exported
    # something; flat sync snapshots keep the classic layout.
    async_aggs = (val("async.aggregations_total")
                  or val("fleetsim.async_aggregations_total"))
    stale = (snapshot.get("async.staleness")
             or snapshot.get("fleetsim.async_staleness"))
    if not (isinstance(stale, dict) and stale.get("count")):
        stale = None
    if async_aggs or stale:
        lines.append("")
        lines.append("async plane")
        aps = (rate("async.aggregations_total")
               or rate("fleetsim.async_aggregations_total"))
        lines.append(f"  aggregations     {async_aggs:>12.0f}"
                     + (f"   ({aps:.3f}/s)" if aps is not None else ""))
        buf_k = (val("async.buffer_target")
                 or val("fleetsim.async_buffer_size"))
        if buf_k:
            lines.append(f"  buffer K         {buf_k:>12.0f}")
        arr_s = val("async.arrival_rate_per_s")
        if arr_s:
            lines.append(f"  arrival rate     {arr_s:>12.3f}/s")
        arr_min = val("fleetsim.async_arrival_rate_per_min")
        if arr_min:
            lines.append(f"  arrival rate     {arr_min:>12.3f}/min")
        discards = (val("async.updates_discarded_stale")
                    or val("fleetsim.async_updates_discarded_total"))
        lines.append(f"  stale discards   {discards:>12.0f}")
        if stale:
            lines.append(
                f"  staleness        p50 {stale.get('p50', 0.0):.1f}   "
                f"p90 {stale.get('p90', 0.0):.1f}   "
                f"p99 {stale.get('p99', 0.0):.1f}")
        mass_f = (val("async.contribution_mass{outcome=folded}")
                  or val("fleetsim.async_contribution_mass"
                         "{outcome=folded}"))
        mass_d = (val("async.contribution_mass{outcome=discarded}")
                  or val("fleetsim.async_contribution_mass"
                         "{outcome=discarded}"))
        if mass_f or mass_d:
            lines.append(f"  mass folded      {mass_f:>12.2f}"
                         f"   discarded {mass_d:.2f}")
        pump_rows = [
            f"{st} {val(f'async.pumps{{state={st}}}'):.0f}"
            for st in ("wait", "train", "retry", "pruned", "evicted")
            if snapshot.get(f"async.pumps{{state={st}}}") is not None]
        if pump_rows:
            lines.append("  pumps            " + "   ".join(pump_rows))
    # Learning plane (the convergence observatory): shown only when a
    # --learn-observe run exported learn.* gauges; default snapshots
    # keep the classic layout.
    upd_norm = snapshot.get("learn.update_norm")
    if upd_norm is not None and not isinstance(upd_norm, dict):
        lines.append("")
        lines.append("learning")
        lines.append(f"  update norm      {float(upd_norm):>12.6f}")
        ewma = val("learn.update_norm_ewma")
        if ewma:
            lines.append(f"  norm ewma        {ewma:>12.6f}")
        step = val("learn.step_size")
        if step:
            lines.append(f"  step size        {step:>12.6f}")
        cos = snapshot.get("learn.cos_prev")
        if cos is not None and not isinstance(cos, dict):
            lines.append(f"  cos(prev update) {float(cos):>12.4f}")
        skew = snapshot.get("learn.cohort_skew")
        if skew is not None and not isinstance(skew, dict):
            lines.append(f"  cohort skew      {float(skew):>12.4f}")
        trend_rows = [
            f"{t} {val(f'learn.trend_total{{trend={t}}}'):.0f}"
            for t in ("warmup", "progress", "plateau", "oscillation",
                      "divergence")
            if snapshot.get(f"learn.trend_total{{trend={t}}}") is not None]
        if trend_rows:
            lines.append("  trends           " + "   ".join(trend_rows))
    compiles = val("telemetry.compile_total")
    recompiles = val("telemetry.recompile_total")
    if compiles or recompiles:
        lines.append("")
        lines.append(f"xla compiles        {compiles:>12.0f}   "
                     f"recompiles {recompiles:.0f}")
    hbm = snapshot.get("runtime.hbm_bytes_in_use")
    if hbm is not None and not isinstance(hbm, dict):
        limit = snapshot.get("runtime.hbm_bytes_limit") or 0.0
        pct = f" ({100.0 * hbm / limit:.1f}%)" if limit else ""
        lines.append("")
        lines.append(f"hbm in use          {hbm / 2**30:>11.3f}G{pct}")
    return "\n".join(lines)
