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
- **Which part of the program is this operation?**  The compiled
  program's instructions carry ``op_name`` metadata built from what the
  program wrote: module and method names, ``telemetry.device_scope``s,
  jax's own wrappers.  :meth:`CompileTracker.scopes` and
  :func:`program_scopes` read them from the same AOT object into
  ``{instruction name: Scope(phase, path)}``, on first ask and never
  before, so that a device trace, which names operations by instruction,
  can be split by the program's own scopes (``colearn trace-summary``,
  the benchmark's ``*_ms_per_round`` readers).
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
from typing import NamedTuple, Optional

from colearn_federated_learning_tpu.telemetry.registry import (
    MetricsRegistry,
    get_registry,
)
from colearn_federated_learning_tpu.telemetry.tracer import declared_scopes

__all__ = [
    "CompileTracker",
    "EventLog",
    "MetricsExporter",
    "Scope",
    "compiled_cost",
    "parse_hlo_text",
    "parse_op_name",
    "program_scopes",
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
    table = getattr(_tracked, "table", None)
    if table is not None and event == "/jax/compilation_cache/cache_hits":
        table["loaded"] += 1
    call = getattr(_tracked, "call", None)
    if call is None:
        return                      # not in a call of the program
    fn, registry = call
    reg = registry if registry is not None else get_registry()
    if event == "/jax/compilation_cache/cache_hits":
        reg.counter("telemetry.cache_hit_total", labels={"fn": fn}).inc()
    elif event == "/jax/compilation_cache/cache_misses":
        reg.counter("telemetry.cache_miss_total", labels={"fn": fn}).inc()


def _on_compile_duration(event: str, duration: float, **_) -> None:
    table = getattr(_tracked, "table", None)
    if table is not None and event == (
            "/jax/core/compile/backend_compile_duration"):
        table["compiled"] += 1      # an executable was built or loaded


@functools.cache
def _listen_for_cache_events() -> None:
    """One pair of listeners for the life of the process, registered by
    the first tracked call."""
    from jax import monitoring

    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_duration_secs_listener(_on_compile_duration)


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


@contextlib.contextmanager
def _table_compile():
    """While the block runs on this thread, no program's cache counters
    move (a scope table's compile is not the round's), and what jax did
    for it is counted here: executables built or loaded, and how many of
    those the persistent cache supplied."""
    _listen_for_cache_events()
    previous = (getattr(_tracked, "call", None),
                getattr(_tracked, "table", None))
    _tracked.call, _tracked.table = None, {"compiled": 0, "loaded": 0}
    try:
        yield _tracked.table
    finally:
        _tracked.call, _tracked.table = previous


# ------------------------------------------------------------ scope table --
class Scope(NamedTuple):
    """Where one instruction of a compiled program lies in the program
    that was written: ``phase`` is ``forward`` (under ``jvp(``),
    ``backward`` (under ``transpose(jvp(``), ``remat`` (under
    ``rematted_computation``, in whichever pass) or ``none``; ``path`` is
    the program's own names, outermost first."""

    phase: str
    path: tuple[str, ...]


NO_SCOPE = Scope("none", ())
_WRAPPED = re.compile(r"^([\w.\-]*)\((.*)\)$")
# Names jax itself puts into an op_name: control flow, calls, checkpoints.
_JAX_NAMES = re.compile(
    r"^(while|body|cond|closed_call|core_call|checkpoint|remat|"
    r"rematted_computation|pjit|jit|scan|shard_map|custom_jvp_call|"
    r"custom_vjp_call|custom_vjp_call_jaxpr|custom_lin|branch_\d+_fun)$")


def parse_op_name(op_name: str) -> Scope:
    """``jit(round_fn)/local/vmap()/while/body/closed_call/transpose(jvp(
    NemotronH))/jvp(NemotronH)/checkpoint/rematted_computation/layer_3/moe/
    mixer/mixer.routed_latent/while/body/closed_call/gather`` ->
    ``Scope("remat", ("local", "NemotronH", "layer_3", "moe", "mixer",
    "mixer.routed_latent"))``.

    Dropped: jax's own names (``_JAX_NAMES``), the transformations around
    a name (``jvp(X)`` is ``X`` in the forward pass; an empty ``vmap()``
    is nothing), a jitted helper's function name (``jit(_where)``), the
    primitive at the end, and a name that repeats the one before it
    (``transpose(jvp(M))/jvp(M)``).  An ``op_name`` XLA made itself
    (``reduce_sum``, ``copy``) has no path."""
    # Where XLA merged two operations it joined their names with ";".
    op_name = op_name.split(";", 1)[0]
    parts = op_name.split("/")
    path: list[str] = []
    primitive_kept = False
    for part in parts:
        name = part
        while (wrapped := _WRAPPED.match(name)) is not None:
            name = "" if wrapped.group(1) in ("jit", "pjit") else (
                wrapped.group(2))
        kept = (bool(name) and _JAX_NAMES.match(name) is None
                and path[-1:] != [name])
        if kept:
            path.append(name)
        primitive_kept = kept and name == part
    if primitive_kept:
        path.pop()                  # the last part, bare: the primitive
    if "rematted_computation" in parts:
        phase = "remat"
    elif "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "none"
    return Scope(phase, tuple(path))


_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# The computations an instruction runs: a loop's body, a fusion's, ...
_CALLED = re.compile(
    r"\b(?:body|condition|calls|to_apply|true_computation|"
    r"false_computation|branch_computations|called_computations)="
    r"(?:%?([\w.\-]+)|\{([^}]*)\})")
# A name's location is followed by its call site, a file's by a line.
_LOCATION = re.compile(r'loc\("([^"]*)"\(')


def parse_hlo_text(text: str) -> tuple[str, dict[str, Scope]]:
    """A compiled program's text (``Compiled.as_text()``: optimized HLO)
    as its module name and ``{instruction name: Scope}``, every
    computation's instructions alike (names are unique in a module).  A
    fusion has the one ``op_name`` XLA gave it, whatever it fused.  An
    instruction whose own ``op_name`` gives no path (XLA made it: a
    ``ragged-dot`` custom call, a ``sort``, a ``copy``; or it has none) is
    filed under the instruction that runs its computation, the nearest
    with a path: the ``while`` of the loop whose body holds it.  What is
    left with ``NO_SCOPE`` lies in no named loop."""
    found = _MODULE.match(text)
    table: dict[str, Scope] = {}
    parsed: dict[str, Scope] = {"": NO_SCOPE}   # a few thousand distinct
    home: dict[str, str] = {}           # instruction -> its computation
    runs: dict[str, str] = {}           # computation -> what runs it
    computation = ""
    for line in text.splitlines():
        instruction = _INSTRUCTION.match(line)
        if instruction is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                computation = header.group(1)
            continue
        name = instruction.group(1)
        op_name = _OP_NAME.search(line)
        op_name = op_name.group(1) if op_name else ""
        if op_name not in parsed:
            parsed[op_name] = parse_op_name(op_name)
        table[name] = parsed[op_name]
        home[name] = computation
        for one, several in _CALLED.findall(line):
            for called in (one or several).replace("%", "").split(","):
                runs.setdefault(called.strip(), name)
    for name, scope in table.items():
        runner = name
        while not scope.path and (runner := runs.get(home[runner])):
            scope = table[runner]
        if scope.path:
            table[name] = scope
    return (found.group(1) if found else ""), table


def _names(scopes) -> set[str]:
    """Every name on the path of any of ``scopes``."""
    return {name for scope in set(scopes) for name in scope.path}


@contextlib.contextmanager
def _metadata_in_cache_key():
    """jax's persistent cache strips debug information from its key
    (``jax/_src/cache_key.py``), so a program that differs from an older
    checkout's in scopes alone loads that checkout's executable, names
    and all.  Inside this block the key holds them.  Process-wide, like
    every ``jax.config.update``: a compile another thread makes meanwhile
    is keyed likewise, which costs it a miss at worst."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    previous = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, previous)


# A compiler option at its default: with any option ``Lowered.compile``
# goes to the compiler (and its persistent cache) and not to jax's memo of
# executables, which holds the one that ran.
_COMPILE_ANEW = {"xla_dump_hlo_as_text": False}

# The newest tracker called under each name, for ``program_scopes``.
_programs: dict[str, "CompileTracker"] = {}


def _abstract(leaf):
    """An argument as ``lower`` takes it in an array's place: shape, dtype,
    weak type and, where the array was committed to one (an uncommitted
    array lowers as unspecified), its sharding.  ``None`` and Python
    scalars are leaves jax keeps as they are."""
    import jax

    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, weak_type=leaf.weak_type,
            sharding=leaf.sharding if leaf.committed else None)
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return leaf


def program_scopes() -> dict[str, dict[str, Scope]]:
    """``{module name: {instruction name: Scope}}`` of every tracked
    program that has run in this process, under the names a device trace's
    ``XLA Modules`` line gives them (``jit_round_fn``, ``jit_body``,
    ``jit_eval_fn``).  Built on first ask (:meth:`CompileTracker.scopes`);
    nothing on ``fit()``'s path asks."""
    tables = {}
    for tracker in list(_programs.values()):
        module, table = tracker._scope_table()
        if table:
            tables[module] = table
    return tables


class CompileTracker:
    """Transparent wrapper around a (jitted) callable that counts the
    distinct call signatures it has seen.

    ``tracker(...)`` forwards to the wrapped fn; attribute access
    (``.lower``, ``.trace`` …) passes through, so code holding the
    tracker can keep using the jit AOT surface.  ``compiles`` is the
    number of distinct signatures, plus every executable jit built for
    a signature it had already seen — the executable count a correct
    static-shape pipeline holds at exactly 1 per sweep shape.

    From its first call the tracker keeps that call's arguments in the
    abstract (no array) and stands under its name in a process-wide map,
    one entry a name, until the next tracker of that name is called: so
    :func:`program_scopes` finds the program after its learner is gone,
    and the jitted function, what it closes over and its executables stay
    alive until then.
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
        self._first_call: Optional[tuple] = None   # (args, kwargs), abstract
        self._aot_cache: dict = {}      # signature -> (lowered, compiled, s)
        self._scope_tables: dict = {}   # signature -> (module name, table)
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
        if self._first_call is None:
            import jax

            # Before the call: it may donate its arguments.
            self._first_call = jax.tree.map(_abstract, (args, kwargs))
            _programs[self.name] = self
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

    # -- the executable, ahead of time ----------------------------------
    def _aot(self, args: tuple, kwargs: dict) -> tuple:
        """``(lowered, compiled, seconds)`` for this signature: lowered and
        compiled once, whoever asks (cost analysis, scope table)."""
        sig = abstract_signature(args, kwargs)
        with self._lock:
            cached = self._aot_cache.get(sig)
        if cached is None:
            cached = _lower_and_compile(self._fn, args, kwargs)
            with self._lock:
                self._aot_cache[sig] = cached
        return cached

    def cost_analysis(self, *args, **kwargs) -> dict:
        """XLA ``cost_analysis`` of the executable for THIS signature
        (AOT lower+compile; cached per signature so repeated asks are
        free).  Returns ``{}`` when the wrapped fn has no ``lower``."""
        if not hasattr(self._fn, "lower"):
            return {}
        _, compiled, seconds = self._aot(args, kwargs)
        return _cost_of(compiled, seconds)

    def scopes(self) -> dict[str, Scope]:
        """``{instruction name: Scope}`` of the program the first call
        compiled, from the text of its executable; ``{}`` before any call
        or where the wrapped fn has no ``lower``.  Built on first ask and
        kept per signature.

        Of this source, whatever the compile cache holds: the text must
        show every ``declared_scopes()`` name the lowered program entered.
        An executable that does not (the persistent cache supplied one an
        older checkout built from the same program under other names) is
        compiled once more with the metadata in the cache key.  Stale
        module names after a rename that leaves the declared set as it was
        are not caught.  That second executable is another object than the
        one that runs, compiled from the same program: instruction names
        agree as far as XLA's passes are deterministic.

        Not the round's compile: it runs outside this tracker's
        ``__call__`` and every ``tracked_call``, so ``telemetry.
        compile_total`` and ``cache_miss_total`` of this ``fn`` stay as
        they were.  Counted on its own: ``telemetry.scope_table_total{fn,
        how=loaded|built}`` (built: the compiler ran), ``telemetry.
        scope_table_seconds{fn}``, gauges ``telemetry.
        scope_table_instructions{fn}`` and ``..._unnamed{fn}``."""
        return self._scope_table()[1]

    def _scope_table(self) -> tuple[str, dict[str, Scope]]:
        if self._first_call is None or not hasattr(self._fn, "lower"):
            return "", {}
        args, kwargs = self._first_call
        sig = abstract_signature(args, kwargs)
        with self._lock:
            cached = self._scope_tables.get(sig)
        if cached is not None:
            return cached
        t0 = time.perf_counter()
        with _table_compile() as did:
            lowered, compiled, _ = self._aot(args, kwargs)
            module, table = parse_hlo_text(compiled.as_text())
            entered = declared_scopes() & _names(map(parse_op_name, set(
                _LOCATION.findall(lowered.as_text(debug_info=True)))))
            if entered - _names(table.values()):
                with _metadata_in_cache_key():
                    _, compiled, _ = _lower_and_compile(
                        self._fn, args, kwargs, _COMPILE_ANEW)
                module, table = parse_hlo_text(compiled.as_text())
        labels = {"fn": self.name}
        reg = self._reg()
        reg.counter("telemetry.scope_table_total", labels={
            **labels,
            "how": "built" if did["compiled"] > did["loaded"] else "loaded",
        }).inc()
        reg.counter("telemetry.scope_table_seconds", labels=labels).inc(
            time.perf_counter() - t0)
        reg.gauge("telemetry.scope_table_instructions", labels=labels).set(
            len(table))
        reg.gauge("telemetry.scope_table_unnamed", labels=labels).set(
            sum(not scope.path for scope in table.values()))
        with self._lock:
            self._scope_tables[sig] = (module, table)
        return module, table


def _lower_and_compile(fn, args: tuple, kwargs: dict,
                       compiler_options: Optional[dict] = None) -> tuple:
    """The one AOT path: ``(lowered, compiled, seconds)``."""
    t0 = time.perf_counter()
    lowered = fn.lower(*args, **kwargs)
    compiled = lowered.compile(compiler_options)
    return lowered, compiled, time.perf_counter() - t0


def _cost_of(compiled, compile_s: float) -> dict:
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
    out = {k: float(v) for k, v in cost.items()
           if isinstance(v, (int, float))}
    out["compile_s"] = compile_s
    return out


def compiled_cost(fn, *args, **kwargs) -> dict:
    """Lower + AOT-compile ``fn`` for these operands and return XLA's
    ``cost_analysis`` dict plus ``compile_s``.  ``{}``-valued keys when
    the backend reports nothing (CPU often does).  NOTE: XLA counts a
    while/scan body ONCE — callers whose FLOPs live in a scan must scale
    by the trip count themselves."""
    if not hasattr(fn, "lower"):
        return {}
    _, compiled, seconds = _lower_and_compile(fn, args, kwargs)
    return _cost_of(compiled, seconds)


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
