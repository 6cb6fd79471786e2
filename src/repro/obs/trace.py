"""Structured trace layer: nested spans on the profiler's clock, layer
scopes for lowered plans, text render.

One :class:`Observer` object is threaded through the engine (driver,
router, lowering, exchange layer) instead of a global — tests construct
their own and assert on the exact spans a code path emitted.  A span is a
named, timed interval with attributes and children; an event is an
instant (zero-duration) child.  The driver records per-query spans (route
decision, plan-cache hit/miss, bind / dispatch / fetch), the lowering
records its semi-join decisions, and the exchange layer emits one
trace-time event per collective exchange (fired during the XLA trace,
i.e. once per compiled specialization — static shapes, capacities and
wire formats).

Export: every ``with obs.span(...)`` also opens a
``jax.profiler.TraceAnnotation`` for the span's lifetime, which is a
no-op unless a profiler session is active.  While one is (``jax.profiler
.trace(dir, create_perfetto_trace=True)``), the spans land in its
``.xplane.pb`` on the same clock as the device's operations, and in its
``perfetto_trace.json.gz``, which https://ui.perfetto.dev loads.
Instant events and manually managed spans (:meth:`Observer.open_span`)
stay in the span tree only.  :meth:`Observer.pretty` renders the tree as
indented text for terminals and tests.

:func:`layer` names the engine layer (``scan``, ``semijoin``,
``aggregate``, ``topk``) of the operations a lowered plan emits, in the
``op_name`` metadata of each compiled operation.

A disabled observer (``enabled=False``) swallows everything through a
shared null span, so instrumented code paths need no ``if`` guards; the
companion :class:`~repro.obs.metrics.MetricsRegistry` rides on the same
object (``obs.metrics``) so every instrumented site can emit both.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Optional

import jax

from repro.obs.metrics import MetricsRegistry

# root spans retained (FIFO): benchmark loops run thousands of queries and
# must not grow the trace without bound; exports see the most recent window
MAX_ROOT_SPANS = 1024


def layer(name: str):
    """``with layer("scan"): ...`` — a ``jax.named_scope`` naming the engine
    layer of the operations traced inside it; the name becomes the first
    component of their ``op_name`` under the jitted plan
    (``jit(run)/shard_map/scan/...``), in the compiled program's metadata
    and in a profiler trace's ``tf_op`` stat.  Only metadata changes: the
    compiled program is the same with or without it.

    The lowering opens one per operator, after the operator's child has
    been evaluated, so layers never nest.  A packed column that is
    decoded lazily (on first touch) takes the layer of the operator that
    first reads it."""
    return jax.named_scope(name)


@dataclasses.dataclass
class Span:
    """One timed interval.  ``t0``/``dur`` are seconds relative to the
    observer's epoch; attributes are plain data (the plain scalars among
    them become the profiler annotation's arguments)."""

    name: str
    cat: str = "query"
    t0: float = 0.0
    dur: float = 0.0
    attrs: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list)

    def set(self, **attrs) -> "Span":
        """Attach attributes mid-span (tier decided during execution)."""
        self.attrs.update(attrs)
        return self

    @property
    def instant(self) -> bool:
        return self.dur == 0.0 and not self.children

    def find(self, name: str) -> list:
        """All spans/events named ``name`` in this subtree (pre-order)."""
        out = [self] if self.name == name else []
        for c in self.children:
            out.extend(c.find(name))
        return out


class _NullSpan:
    """Shared do-nothing span handle for a disabled observer."""

    __slots__ = ()

    def set(self, **attrs):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _SpanContext:
    """Context manager driving one live span on the observer's stack and a
    profiler annotation of the same name for its lifetime."""

    __slots__ = ("obs", "span", "annotation")

    def __init__(self, obs: "Observer", span: Span):
        self.obs = obs
        self.span = span
        self.annotation = jax.profiler.TraceAnnotation(
            span.name, **{k: v for k, v in span.attrs.items()
                          if isinstance(v, (int, float, str, bool))})

    def __enter__(self) -> Span:
        self.obs._stack.append(self.span)
        self.annotation.__enter__()
        return self.span

    def __exit__(self, exc_type, exc, tb):
        self.annotation.__exit__(exc_type, exc, tb)
        span = self.obs._stack.pop()
        span.dur = self.obs._now() - span.t0
        if exc_type is not None:
            span.attrs["error"] = f"{exc_type.__name__}: {exc}"
        if self.obs._stack:
            self.obs._stack[-1].children.append(span)
        else:
            self.obs.spans.append(span)
        return False


class Observer:
    """The engine's observability hub: a span stack plus a metrics
    registry, explicitly threaded (never a global).

    ``enabled=False`` turns the trace layer off (spans become no-ops and
    nothing is retained) while the metrics registry stays live — counters
    are the always-on tier, traces the on-by-default-but-droppable one.

    The span stack is PER-THREAD: the serving tier records spans from the
    asyncio event loop and its dispatch executor concurrently, and a
    shared stack would interleave their push/pop sequences (a worker's
    ``execute`` span would pop the event loop's half-open request span).
    Each thread nests independently; completed roots from every thread
    land in the one shared ``spans`` deque (append is atomic).
    """

    def __init__(self, enabled: bool = True,
                 metrics: Optional[MetricsRegistry] = None):
        self.enabled = enabled
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.spans: deque = deque(maxlen=MAX_ROOT_SPANS)  # completed roots
        self._tls = threading.local()
        self._epoch = time.perf_counter()

    @property
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _now(self) -> float:
        return time.perf_counter() - self._epoch

    # -- recording ----------------------------------------------------------
    def span(self, name: str, cat: str = "query", **attrs):
        """``with obs.span("execute", source="q6") as sp: ...`` — nested
        spans attach to the innermost open span, top-level spans to
        ``obs.spans``."""
        if not self.enabled:
            return _NULL_SPAN
        return _SpanContext(self, Span(name=name, cat=cat, t0=self._now(),
                                       attrs=dict(attrs)))

    def event(self, name: str, cat: str = "query", **attrs) -> None:
        """Instant event, attached like a zero-duration child span."""
        if not self.enabled:
            return
        ev = Span(name=name, cat=cat, t0=self._now(), attrs=dict(attrs))
        if self._stack:
            self._stack[-1].children.append(ev)
        else:
            self.spans.append(ev)

    def open_span(self, name: str, cat: str = "query", **attrs):
        """Manually managed span for call sites that cannot scope a
        ``with`` block to one thread's stack — an asyncio task's request
        span stays open across ``await`` points while OTHER tasks on the
        same thread open and close theirs, so stack-nested spans would
        pop in the wrong order.  The returned span is detached (never on
        any stack); finish it with :meth:`close_span`."""
        if not self.enabled:
            return _NULL_SPAN
        return Span(name=name, cat=cat, t0=self._now(), attrs=dict(attrs))

    def close_span(self, span) -> None:
        """Finish a span from :meth:`open_span`: stamp its duration and
        retain it as a root."""
        if span is _NULL_SPAN or not self.enabled:
            return
        span.dur = self._now() - span.t0
        self.spans.append(span)

    def clear(self) -> None:
        self.spans.clear()
        self._tls = threading.local()  # drops every thread's open stack

    # -- querying (tests assert on these) -----------------------------------
    def find(self, name: str) -> list:
        """All recorded spans/events named ``name``, across all roots."""
        out = []
        for s in self.spans:
            out.extend(s.find(name))
        return out

    def last(self, name: str) -> Optional[Span]:
        hits = self.find(name)
        return hits[-1] if hits else None

    # -- rendering ---------------------------------------------------------
    def pretty(self) -> str:
        """Indented text rendering of every retained root span."""
        lines = []

        def _fmt_attrs(attrs: dict) -> str:
            if not attrs:
                return ""
            body = ", ".join(f"{k}={v}" for k, v in attrs.items())
            return f"  [{body}]"

        def _walk(span: Span, depth: int):
            pad = "  " * depth
            if span.instant:
                lines.append(f"{pad}* {span.name}{_fmt_attrs(span.attrs)}")
            else:
                lines.append(f"{pad}{span.name}: {span.dur * 1e3:.3f} ms"
                             f"{_fmt_attrs(span.attrs)}")
            for c in span.children:
                _walk(c, depth + 1)

        for root in self.spans:
            _walk(root, 0)
        return "\n".join(lines)
