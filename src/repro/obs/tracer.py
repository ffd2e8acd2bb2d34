"""In-process tracing + metrics plane (docs/observability.md).

One process-wide tracer records **spans** (named, nestable, monotonic-
clock timed, thread-attributed), **instants** (zero-duration marks),
**counters** (monotonic sums) and **gauges** (last-value samples) from
every layer of the stack — the Newton outer loop, the host-driven
streamed PCG, HVP/kernel dispatch, the chunk prefetch pipeline, the
robustness machinery and the serving plane all emit into the same
vocabulary, so one Perfetto timeline (or one summary table) covers a
solve end to end.

Contract:

* **Near-zero overhead when disabled.** The module-level ``span`` /
  ``instant`` / ``count`` / ``gauge`` functions delegate to a process
  global that defaults to :class:`NoopTracer`, whose ``span`` returns a
  cached do-nothing context manager — a disabled instrumentation site
  costs two attribute lookups and a couple of no-op calls, nothing else
  (the ``benchmarks/bench_obs.py`` gate holds this to ≤2% on a tight
  solve loop).
* **Thread safety.** Events are appended under a lock with the emitting
  thread's id and name — the chunk-prefetch producer thread and the
  consumer interleave into one consistent timeline.
* **A closed vocabulary.** Every span/instant kind must be registered
  in :data:`SPAN_KINDS` (counters in :data:`COUNTER_KINDS`, gauges in
  :data:`GAUGE_KINDS`); an unknown name raises immediately. The
  rendered registry is embedded in docs/observability.md and checked by
  ``tools/docs_check.py`` — the same drift gate as the HVP support
  matrix.

* **On the profiler's clock.** An enabled span also enters a
  ``jax.profiler.TraceAnnotation`` named by its kind, with its opening
  args as event stats, so a ``jax.profiler`` capture shows the program's
  spans on the host plane beside the device operations. Only the
  enabled tracer imports ``jax.profiler``. Instants and
  :meth:`Tracer.complete` spans (recorded after the fact) stay in memory
  only.

Enable with ``REPRO_TRACE=1`` in the environment (read at import), with
``DiscoConfig(trace=True)`` (the solver calls :func:`enable` at
construction), or programmatically via :func:`enable`.
"""
from __future__ import annotations

import os
import threading
import time
from typing import NamedTuple

# ---------------------------------------------------------------------------
# the registry: every kind an instrumentation site may emit
# ---------------------------------------------------------------------------

#: span / instant registry: kind -> (layer, event type, description).
#: ``span`` kinds carry a duration; ``instant`` kinds are zero-duration
#: marks. The docs embed exactly :func:`render_span_kinds`.
SPAN_KINDS: dict[str, tuple[str, str, str]] = {
    "newton.outer": (
        "core", "span",
        "one damped-Newton outer iteration: the whole loop body of "
        "`fit` (fault hook, key split, step, stats, ledger, checkpoint, "
        "convergence test)"),
    "newton.step": (
        "core", "span",
        "the jitted outer step inside `newton.outer`: dispatch, "
        "`block_until_ready` and the stats' host conversion (the extent "
        "of `iter_s`)"),
    "disco.place": (
        "core", "span",
        "the solver's data put on the mesh at construction, each device "
        "sent only its own shards, until the copies are done (args: "
        "`bytes` placed in all, `shards` on the mesh axis)"),
    "pcg.round": (
        "core", "span",
        "one host-driven streamed PCG round (classic iteration or "
        "s-step block), synced to completion"),
    "comm.allreduce": (
        "core", "instant",
        "one paper-style communication round, emitted at the call site "
        "of the streamed path (outer margins/gradient + per PCG round) "
        "— the events the rounds-match gate counts against CommLedger"),
    "hvp.apply": (
        "core", "span",
        "one streamed Hessian-vector product (a full prefetched pass "
        "over the store; `multi` marks the batched s-step form)"),
    "hvp.dispatch": (
        "core", "instant",
        "HVP operator registry cell resolved at solver setup "
        "(core/hvp.py cell id in `cell`)"),
    "kernel.dispatch": (
        "kernels", "instant",
        "Pallas kernel execution mode resolved (auto/native/interpret/"
        "ref), emitted once per distinct mode seen"),
    "stream.pass": (
        "data", "span",
        "one prefetched pass of the chunk schedule (label = stream "
        "kind, `+hvp` for mixed-precision HVP staging)"),
    "stream.chunk_load": (
        "data", "span",
        "one chunk read + ELL tile build in the prefetch producer "
        "thread (args: cid, shard, layouts)"),
    "store.chunk_read": (
        "data", "span",
        "one ShardStore CSR chunk materialized (memmap open + optional "
        "CRC32 verification; nested inside stream.chunk_load on the "
        "streamed path)"),
    "io.retry": (
        "robust", "instant",
        "a transient I/O failure caught by the retry policy (args: "
        "attempt index, error type)"),
    "ckpt.write": (
        "robust", "span",
        "one atomic checkpoint snapshot write (stage + fsync + rename "
        "protocol of robust/checkpoint.py)"),
    "robust.replan": (
        "robust", "instant",
        "an elastic re-plan fired: the chunk->shard schedule was "
        "swapped on measured seconds (args mirror ReplanEvent)"),
    "registry.publish": (
        "serve", "span",
        "one model registry version staged, fsync'd, renamed and "
        "(optionally) activated"),
    "serve.hot_swap": (
        "serve", "span",
        "the scoring engine swapped in a newly activated registry "
        "version between ticks"),
    "serve.tick": (
        "serve", "span",
        "one scheduler tick: admit -> score -> complete (args: tick "
        "index, scored count)"),
    "serve.pack": (
        "serve", "span",
        "one batch packed into (id, value) slots on the host "
        "(`RequestPacker.pack`; arg `k`: slots per request; nested "
        "inside serve.tick)"),
    "serve.copy_in": (
        "serve", "span",
        "the packed slots copied to the device, until both arrays are "
        "ready"),
    "serve.kernel": (
        "serve", "span",
        "the scoring step (`slot_margins`: gather, multiply, row sum) "
        "dispatched and waited for"),
    "serve.copy_out": (
        "serve", "span",
        "the margins copied back to the host"),
}

#: counter registry: name -> description. Counters are monotone sums.
COUNTER_KINDS: dict[str, str] = {
    "comm.rounds": (
        "paper-style communication rounds. In-memory solves tally the "
        "analytic per-iteration cost; streamed solves count at the "
        "actual call sites — the independent tally the bench_obs gate "
        "cross-validates against CommLedger.rounds"),
    "comm.floats": "floats communicated (analytic tally, both paths)",
    "comm.spmd_collectives": (
        "SPMD collective launches (analytic tally, both paths)"),
    "disco.place_bytes_max": (
        "bytes of the solver's placed data held by its most-loaded "
        "device, summed over the shards of every array `disco.place` "
        "put (a replicated array counts on each device)"),
    "disco.hvp_slot_bytes": (
        "bytes of the (id, value) slot layouts and head slab a sparse "
        "in-memory solver placed for its HVP, all shards; 0 when it "
        "chose blocked-ELL tiles"),
    "disco.hvp_one_pass_bytes": (
        "bytes of X that an in-memory solver's local products read once "
        "per HVP through the one-pass dense kernel, all shards; 0 when "
        "its HVP takes two passes (off a TPU, sparse layouts, a panel "
        "that does not fit VMEM)"),
    "io.retries": "transient I/O failures retried by the retry policy",
    "serve.scored": "requests scored by the micro-batch scheduler",
    "serve.pack_bytes": (
        "bytes of the packed (id, value) slots staged to the device, "
        "summed over packs: `batch x k x 8` per float32 pack"),
    "serve.queue_wait_s": (
        "seconds the scored requests waited in the scheduler's queue, "
        "submit to admission, on the scheduler's clock (over "
        "`serve.scored`: the mean wait)"),
}

#: gauge registry: name -> description. Gauges record last-value samples.
GAUGE_KINDS: dict[str, str] = {
    "serve.queue_depth": (
        "scheduler waiting-queue depth, sampled at the top of each "
        "tick"),
}


class TraceEvent(NamedTuple):
    """One recorded trace event.

    ``ph`` is ``'X'`` (complete span) or ``'i'`` (instant), matching the
    Chrome trace-event phases the exporter emits; times are
    ``time.perf_counter_ns()`` values (monotonic).
    """

    kind: str
    ph: str            # 'X' span | 'i' instant
    t0_ns: int         # span start (or instant time), perf_counter_ns
    dur_ns: int        # span duration (0 for instants)
    tid: int           # emitting thread id
    thread: str        # emitting thread name
    args: dict


def _check(kind: str, registry: dict, what: str) -> None:
    if kind not in registry:
        raise ValueError(
            f"unregistered {what} {kind!r} — add it to "
            f"repro.obs.tracer.{ {'span kind': 'SPAN_KINDS', 'counter': 'COUNTER_KINDS', 'gauge': 'GAUGE_KINDS'}[what] } "
            "(and to docs/observability.md; tools/docs_check.py gates "
            "the two against each other)")


class _NoopSpan:
    """The cached do-nothing context manager of the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        """No-op twin of :meth:`Span.set`."""


_NOOP_SPAN = _NoopSpan()


class NoopTracer:
    """Disabled tracer: every operation is a no-op.

    ``span`` returns one cached :class:`_NoopSpan` instance, so an
    instrumented ``with`` block costs only the context-manager protocol
    — the ≤2% disabled-overhead contract of docs/observability.md.
    """

    enabled = False

    def span(self, kind: str, **args) -> "_NoopSpan":
        """Return the cached no-op span."""
        return _NOOP_SPAN

    def instant(self, kind: str, **args) -> None:
        """Drop an instant event."""

    def complete(self, kind: str, t0_ns: int, **args) -> None:
        """Drop an explicit-start span."""

    def count(self, name: str, value: float = 1) -> None:
        """Drop a counter increment."""

    def gauge(self, name: str, value: float) -> None:
        """Drop a gauge sample."""


class Span:
    """A live span: records one ``'X'`` event when its ``with`` exits.

    Spans nest naturally (enter/exit order is the nesting); use
    :meth:`set` to attach args that are only known inside the block.
    The span is also a ``jax.profiler.TraceAnnotation`` of the same name
    for the duration of the block, carrying the args given at opening
    (those of :meth:`set` reach the in-memory event only).
    """

    __slots__ = ("_tracer", "_kind", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", kind: str, args: dict):
        self._tracer = tracer
        self._kind = kind
        self._args = args
        self._t0 = 0
        self._annotation = None

    def __enter__(self) -> "Span":
        self._annotation = self._tracer._annotation(self._kind,
                                                    **self._args)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._tracer._record(self._kind, "X", self._t0, t1 - self._t0,
                             self._args)
        return False

    def set(self, **args) -> None:
        """Merge ``args`` into the span's args (values learned mid-block,
        e.g. the version id a publish allocated)."""
        self._args.update(args)


class Tracer:
    """Thread-safe in-process tracer (the enabled implementation).

    Events accumulate in :attr:`events` (a list of
    :class:`TraceEvent`), counters in :attr:`counters` and gauges in
    :attr:`gauges` — read them directly, or through the exporters in
    :mod:`repro.obs.export` / the aggregations in
    :mod:`repro.obs.report`. All mutation happens under one lock.
    """

    enabled = True

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self._lock = threading.Lock()
        self.events: list[TraceEvent] = []
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.epoch_ns = time.perf_counter_ns()

    def _record(self, kind: str, ph: str, t0_ns: int, dur_ns: int,
                args: dict) -> None:
        th = threading.current_thread()
        ev = TraceEvent(kind=kind, ph=ph, t0_ns=t0_ns, dur_ns=dur_ns,
                        tid=th.ident or 0, thread=th.name,
                        args=dict(args))
        with self._lock:
            self.events.append(ev)

    def span(self, kind: str, **args) -> Span:
        """Open a span of a registered kind; use as a context manager."""
        _check(kind, SPAN_KINDS, "span kind")
        return Span(self, kind, args)

    def instant(self, kind: str, **args) -> None:
        """Record a zero-duration mark of a registered kind."""
        _check(kind, SPAN_KINDS, "span kind")
        self._record(kind, "i", time.perf_counter_ns(), 0, args)

    def complete(self, kind: str, t0_ns: int, **args) -> None:
        """Record a span whose start ``t0_ns`` (``perf_counter_ns``) was
        captured by the caller — for spans that cannot be a ``with``
        block, e.g. a prefetch pass closed from its context-manager
        exit. It cannot be back-dated into a profiler capture, so it
        stays in memory only."""
        _check(kind, SPAN_KINDS, "span kind")
        t1 = time.perf_counter_ns()
        self._record(kind, "X", t0_ns, t1 - t0_ns, args)

    def count(self, name: str, value: float = 1) -> None:
        """Add ``value`` to a registered counter."""
        _check(name, COUNTER_KINDS, "counter")
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + value

    def gauge(self, name: str, value: float) -> None:
        """Sample a registered gauge (last value wins)."""
        _check(name, GAUGE_KINDS, "gauge")
        with self._lock:
            self.gauges[name] = value

    def span_count(self, kind: str) -> int:
        """Number of recorded events (spans + instants) of ``kind``."""
        with self._lock:
            return sum(1 for e in self.events if e.kind == kind)

    def snapshot(self) -> tuple[list[TraceEvent], dict, dict]:
        """Consistent copy of (events, counters, gauges)."""
        with self._lock:
            return (list(self.events), dict(self.counters),
                    dict(self.gauges))


# ---------------------------------------------------------------------------
# process-global tracer + module-level emission API
# ---------------------------------------------------------------------------

_NOOP = NoopTracer()
_TRACER: Tracer | NoopTracer = _NOOP
if os.environ.get("REPRO_TRACE", "") not in ("", "0"):
    _TRACER = Tracer()


def enable(reset: bool = False) -> Tracer:
    """Install (or return) the process-global :class:`Tracer`.

    ``reset=True`` discards any accumulated events and starts fresh —
    what benchmarks do between measured cases. Returns the active
    tracer so callers can read its events/counters back.
    """
    global _TRACER
    if reset or not isinstance(_TRACER, Tracer):
        _TRACER = Tracer()
    return _TRACER


def disable() -> None:
    """Swap the no-op tracer back in (recorded events are dropped)."""
    global _TRACER
    _TRACER = _NOOP


def enabled() -> bool:
    """True iff tracing is currently enabled."""
    return _TRACER.enabled


def get_tracer() -> Tracer | NoopTracer:
    """The process-global tracer (Noop when disabled)."""
    return _TRACER


def span(kind: str, **args):
    """Open a span on the global tracer (no-op context when disabled).

    The disabled case returns the cached no-op span here, without a
    second call that would pack the args again: ``fit`` opens two spans
    per outer iteration and each scheduler tick five.
    """
    if _TRACER is _NOOP:
        return _NOOP_SPAN
    return _TRACER.span(kind, **args)


def instant(kind: str, **args) -> None:
    """Record an instant on the global tracer."""
    _TRACER.instant(kind, **args)


def complete(kind: str, t0_ns: int, **args) -> None:
    """Record an explicit-start span on the global tracer."""
    _TRACER.complete(kind, t0_ns, **args)


def count(name: str, value: float = 1) -> None:
    """Increment a counter on the global tracer."""
    _TRACER.count(name, value)


def gauge(name: str, value: float) -> None:
    """Sample a gauge on the global tracer."""
    _TRACER.gauge(name, value)


def render_span_kinds() -> str:
    """The docs/observability.md vocabulary block, generated from the
    registries (``tools/docs_check.py`` verifies the docs embed exactly
    this between the ``span-kinds`` markers)."""
    lines = ["| kind | layer | event | description |",
             "|---|---|---|---|"]
    for kind, (layer, event, desc) in SPAN_KINDS.items():
        lines.append(f"| `{kind}` | {layer} | {event} | {desc} |")
    lines.append("")
    lines.append("| counter | description |")
    lines.append("|---|---|")
    for name, desc in COUNTER_KINDS.items():
        lines.append(f"| `{name}` | {desc} |")
    lines.append("")
    lines.append("| gauge | description |")
    lines.append("|---|---|")
    for name, desc in GAUGE_KINDS.items():
        lines.append(f"| `{name}` | {desc} |")
    return "\n".join(lines)
