"""Explicit-clock tracing: spans, events, and the closure-iteration hook.

:class:`Tracer` records **spans** (named intervals with attributes and a
parent link) and **events** (point annotations inside a span) against an
injectable clock, so every layer of the stack — admission, batch window,
planner decision, closure execution — can show where a request's time
went.  The span tree is exported to Chrome ``trace_event`` JSON by
``repro.obs.chrome`` (open it in Perfetto) and summarized by the metrics
layer (``repro.obs.metrics``).

Design constraints (OBSERVABILITY.md has the operator story):

* **Zero overhead when disabled.**  A disabled tracer creates no span
  objects (``span()``/``start_span`` return the shared :data:`NULL_SPAN`
  and record nothing), and the engine compiles *uninstrumented*
  executables — the exact same ``PlanKey`` as before this subsystem
  existed, so the hot path is bit-for-bit the untraced one.  Tests assert
  this contract (tests/test_obs.py).
* **Explicit clock.**  ``clock`` is injectable (fake clocks in tests,
  ``time.perf_counter`` by default); spans never call ``time`` behind the
  caller's back.
* **Cross-thread propagation is explicit.**  The "current span" rides in
  a per-tracer :class:`contextvars.ContextVar` — correct under asyncio
  task interleaving — and :meth:`Tracer.wrap` hands a parent span across
  an executor-thread boundary (the serving loop runs engine work in a
  worker thread).
* **One clock with the device.**  An enabled tracer enters a
  ``jax.profiler.TraceAnnotation("obs.<name>", span_id=<id>)`` around
  every context-managed span (:meth:`Tracer.span`), so under a
  ``jax.profiler`` trace each such span appears on its thread's host line
  of the xplane, on the clock the device operations are stamped with, and
  is matched to its :class:`Span` by ``span_id``.  Spans with an explicit
  lifecycle (:meth:`Tracer.start_span` / :meth:`Tracer.finish`) open and
  close on different code paths, so they get no annotation.

Named compiles
--------------
An enabled tracer turns each of JAX's backend-compile duration events
(``/jax/core/compile/backend_compile_duration``) into a ``compile`` event
(``fun_name``, ``seconds``) on the span current where the compile ran.
One process-wide monitoring listener (:func:`_on_compile`) forwards to
every live enabled tracer, as :func:`emit_iteration` does for iteration
events; a disabled tracer registers nothing.

Closure-iteration events
------------------------
The masked fixpoint loops (core/closure.py, core/semantics.py) accept a
static ``iter_hook`` callable invoked through ``jax.debug.callback`` at
every iteration boundary — inside jit, but host-side, carrying
``(iteration, active_rows, changed, overflow)``.  Compiled executables
bake in ONE process-wide trampoline (:func:`emit_iteration`) rather than
any particular tracer, so instrumented plans stay cacheable; the engine
routes the trampoline to a per-closure-run sink with
:func:`iteration_scope`.  When the hook is ``None`` (uninstrumented
plans) nothing is traced into the executable at all.
"""
from __future__ import annotations

import contextvars
import itertools
import threading
import time
import weakref
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable


@dataclass
class Span:
    """One named interval in the trace (see OBSERVABILITY.md taxonomy)."""

    name: str
    span_id: int
    parent_id: int | None
    t_start: float
    cat: str = ""
    tid: int = 0  # thread the span was opened on (Chrome track)
    t_end: float | None = None
    attrs: dict = field(default_factory=dict)
    #: point events: ``{"name", "t", "args"}`` dicts, in arrival order
    events: list = field(default_factory=list)

    def set(self, **attrs) -> "Span":
        """Attach/overwrite span attributes."""
        self.attrs.update(attrs)
        return self

    def add_event(self, name: str, t: float, **args) -> None:
        self.events.append({"name": name, "t": t, "args": args})

    @property
    def duration_s(self) -> float | None:
        return None if self.t_end is None else self.t_end - self.t_start


class _NullSpan:
    """Inert span returned by a disabled tracer: accepts every call,
    records nothing, and is falsy so callers can gate extra work on it."""

    __slots__ = ()
    name = ""
    span_id = 0
    parent_id = None
    t_start = 0.0
    t_end = None
    events: list = []  # never appended to
    attrs: dict = {}  # never written (set() is a no-op)

    def set(self, **attrs) -> "_NullSpan":
        return self

    def add_event(self, name: str, t: float, **args) -> None:
        pass

    def __bool__(self) -> bool:
        return False


#: the shared inert span of every disabled tracer
NULL_SPAN = _NullSpan()


class _NullSpanContext:
    """What a disabled tracer's :meth:`Tracer.span` returns: one shared
    context manager yielding :data:`NULL_SPAN`, so a disabled span
    builds no generator and enters nothing."""

    __slots__ = ()

    def __enter__(self) -> _NullSpan:
        return NULL_SPAN

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN_CONTEXT = _NullSpanContext()


class Tracer:
    """Span/event recorder with an explicit clock.

    ``enabled=False`` makes every operation a no-op (the zero-overhead
    contract); ``iteration_events`` additionally gates whether the engine
    compiles *instrumented* closure executables that report per-iteration
    progress (see module docstring).  ``max_spans`` bounds memory on long
    serving runs — beyond it new spans are dropped (counted in
    ``dropped``), never partially recorded.
    """

    def __init__(
        self,
        enabled: bool = True,
        clock: Callable[[], float] = time.perf_counter,
        iteration_events: bool = True,
        max_spans: int = 200_000,
    ) -> None:
        self.enabled = enabled
        self.clock = clock
        self.iteration_events = iteration_events
        self.max_spans = max_spans
        self.spans: list[Span] = []
        self.dropped = 0
        self._ids = itertools.count(1)
        self._current: contextvars.ContextVar[Span | None] = (
            contextvars.ContextVar(f"repro_obs_span_{id(self)}", default=None)
        )
        if enabled:
            _watch_compiles(self)

    # ------------------------------------------------------------------ #
    @property
    def wants_iterations(self) -> bool:
        """Should the engine request instrumented closure executables?"""
        return self.enabled and self.iteration_events

    def current(self) -> Span | None:
        """The context's innermost open span (None outside any span)."""
        return self._current.get() if self.enabled else None

    # ------------------------------------------------------------------ #
    def start_span(
        self,
        name: str,
        parent: Span | None = None,
        cat: str = "",
        t_start: float | None = None,
        **attrs,
    ) -> Span:
        """Open a span without making it current (explicit lifecycle: the
        serving loop opens request spans at admission and finishes them at
        future resolution, on different code paths).  ``parent=None``
        links to the context's current span, if any."""
        if not self.enabled:
            return NULL_SPAN  # type: ignore[return-value]
        if len(self.spans) >= self.max_spans:
            self.dropped += 1
            return NULL_SPAN  # type: ignore[return-value]
        if parent is None:
            parent = self._current.get()
        span = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=(
                parent.span_id if isinstance(parent, Span) else None
            ),
            t_start=self.clock() if t_start is None else t_start,
            cat=cat,
            tid=threading.get_ident(),
            attrs=dict(attrs),
        )
        self.spans.append(span)
        return span

    def finish(self, span, t_end: float | None = None, **attrs) -> None:
        """Close a span (idempotent: a second finish is a no-op so shared
        cleanup paths can't double-close)."""
        if not isinstance(span, Span) or span.t_end is not None:
            return
        span.attrs.update(attrs)
        span.t_end = self.clock() if t_end is None else t_end

    def span(
        self,
        name: str,
        parent: Span | None = None,
        cat: str = "",
        t_start: float | None = None,
        **attrs,
    ):
        """Context-managed span that is *current* inside the block: nested
        ``span()`` calls and :meth:`event` attach to it automatically.
        Enabled, the block also runs under a profiler annotation
        ``obs.<name>`` carrying the span's id (module docstring)."""
        if not self.enabled:
            return _NULL_SPAN_CONTEXT
        return self._live_span(name, parent, cat, t_start, attrs)

    @contextmanager
    def _live_span(self, name, parent, cat, t_start, attrs):
        sp = self.start_span(name, parent=parent, cat=cat, t_start=t_start, **attrs)
        if not isinstance(sp, Span):  # over max_spans: dropped
            yield sp
            return
        import jax

        token = self._current.set(sp)
        try:
            with jax.profiler.TraceAnnotation(f"obs.{name}", span_id=sp.span_id):
                if t_start is None:  # stamp beside the annotation's start
                    sp.t_start = self.clock()
                yield sp
        finally:
            self._current.reset(token)
            self.finish(sp)

    def event(self, name: str, **args) -> None:
        """Point event on the context's current span (dropped if none)."""
        if not self.enabled:
            return
        sp = self._current.get()
        if sp is not None:
            sp.add_event(name, self.clock(), **args)

    def wrap(self, parent, fn: Callable) -> Callable:
        """Carry ``parent`` across a thread boundary: the returned callable
        installs it as the current span in the *executing* thread's
        context for the duration of ``fn`` (contexts are per-thread, so
        this can't leak into the caller's)."""
        if not self.enabled or not isinstance(parent, Span):
            return fn

        def inner(*a, **k):
            token = self._current.set(parent)
            try:
                return fn(*a, **k)
            finally:
                self._current.reset(token)

        return inner

    # ------------------------------------------------------------------ #
    def iteration_sink(self, span) -> Callable | None:
        """Sink for :func:`iteration_scope` appending ``iteration`` events
        (iteration index, active-row count, changed units, overflow flag)
        to ``span``.  None when iteration events are off or the span is
        inert — callers pass that straight to ``iteration_scope``."""
        if not self.wants_iterations or not isinstance(span, Span):
            return None

        def sink(it, active_rows, changed, overflow) -> None:
            span.add_event(
                "iteration",
                self.clock(),
                iteration=int(it),
                active_rows=int(active_rows),
                changed=int(changed),
                overflow=bool(overflow),
            )

        return sink

    def clear(self) -> None:
        self.spans.clear()
        self.dropped = 0


# ---------------------------------------------------------------------- #
# Named compiles: one process-wide JAX monitoring listener, registered by
# the first enabled tracer and never removed, forwards each backend
# compile to every live enabled tracer as a ``compile`` event on the span
# current in the compiling thread (tracers are held weakly, so a tracer
# that is gone stops receiving them).
# ---------------------------------------------------------------------- #
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_TRACERS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_compile_listener_registered = False
_compile_lock = threading.Lock()


def _on_compile(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    for tracer in list(_COMPILE_TRACERS):
        tracer.event("compile", fun_name=kw.get("fun_name", ""),
                     seconds=float(duration))


def _watch_compiles(tracer: Tracer) -> None:
    global _compile_listener_registered
    with _compile_lock:
        if not _compile_listener_registered:
            import jax

            jax.monitoring.register_event_duration_secs_listener(_on_compile)
            _compile_listener_registered = True
        _COMPILE_TRACERS.add(tracer)


#: shared disabled tracer — the default wiring of every engine/server, so
#: constructing them never allocates tracing state.
NULL_TRACER = Tracer(enabled=False)


# ---------------------------------------------------------------------- #
# Closure-iteration trampoline.
#
# Instrumented executables (PlanKey.instrumented) bake in `emit_iteration`
# via jax.debug.callback; at run time it forwards to whatever sink the
# innermost `iteration_scope` installed.  The indirection is what lets one
# compiled executable serve every traced closure run (the sink changes per
# run, the baked-in callable never does).  The engine serializes closure
# runs under its own lock, so a plain module global is race-free; the
# scope still save/restores to stay correct under re-entrancy.
# ---------------------------------------------------------------------- #
_ITER_SINK: Callable | None = None


def emit_iteration(it, active_rows, changed, overflow) -> None:
    """Host-side iteration-boundary callback baked into instrumented
    closure executables (see core/closure.py ``iter_hook``)."""
    sink = _ITER_SINK
    if sink is not None:
        sink(it, active_rows, changed, overflow)


@contextmanager
def iteration_scope(sink: Callable | None):
    """Route :func:`emit_iteration` to ``sink`` for the duration of one
    closure run.  On exit (instrumented runs only) pending debug callbacks
    are flushed with ``jax.effects_barrier()`` so no event lands after its
    span closed."""
    global _ITER_SINK
    prev = _ITER_SINK
    _ITER_SINK = sink
    try:
        yield
    finally:
        if sink is not None:
            try:
                import jax

                jax.effects_barrier()
            except Exception:  # pragma: no cover — barrier is best-effort
                pass
        _ITER_SINK = prev
