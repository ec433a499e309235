"""Lifecycle spans: context-manager timing around the runtime's phase
boundaries (reservation, node launch, feed waves, checkpoint save/restore,
serving requests), flushed as structured events into the registry.

A span records wall-clock AND monotonic timestamps — wall time orders events
across processes/hosts in the merged cluster view; the monotonic pair is what
the duration is computed from (NTP steps must not corrupt durations). Each
completed span:

* appends an event dict to the registry's bounded event buffer::

      {"span": name, "ts": wall_start, "dur_s": secs, "ok": bool, **attrs}

* observes its duration into the histogram ``{name}_seconds`` — so spans are
  queryable both as individual events (debugging a slow launch) and as
  distributions (p99 checkpoint-save time), and survive the event buffer's
  bounded window.

A span opened **every step** (placing a batch, waiting for one, dispatching
the step) passes ``seconds_total=<Counter>`` instead: its duration is added
to that counter and nothing else is kept — no event (1024 of them would push
every lifecycle event out of the buffer within minutes), no histogram, and a
span id only while a flight shard is open.

**The profiler's clock.** Where ``jax`` is already imported in the process
(looked up in ``sys.modules``, never imported: the driver and the executor
stay off jax), every span also enters ``jax.profiler.TraceAnnotation``
named ``tos.<name>``, so it lands on the host plane of whatever
``.xplane.pb`` a profiler session writes, beside the device's operations and
on their clock. With no session open the annotation is inactive and costs
about a microsecond. ``step_num=`` makes it a ``StepTraceAnnotation``, which
gives the trace its Steps line.

When the registry is disabled, :func:`span` returns a shared no-op context
manager: no allocation, nothing recorded.
"""

import sys
import threading
import time

from tensorflowonspark_tpu.obs import flight as _flight
from tensorflowonspark_tpu.obs import registry as _registry
from tensorflowonspark_tpu.obs import tracing as _tracing

#: every span's name in a profiler trace starts with this
ANNOTATION_PREFIX = "tos."


class _NullSpan:
    """Shared do-nothing span handed out while collection is disabled."""

    __slots__ = ()
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL = _NullSpan()


def _profiler_annotation(name, step_num):
    """The profiler's annotation for a span, or None in a process that has
    not imported jax (or whose import of it is still under way)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    if step_num is None:
        return profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
    return profiler.StepTraceAnnotation(ANNOTATION_PREFIX + name, step_num=step_num)


class Span:
    """One open span; ``dur_s`` holds its duration once it has closed."""

    __slots__ = (
        "name", "attrs", "dur_s", "_registry", "_seconds_total", "_step_num",
        "_annotation", "_t0_wall", "_t0_mono", "_span_id", "_parent_id",
    )

    def __init__(self, name, registry, attrs, seconds_total=None, step_num=None):
        self.name = name
        self.attrs = attrs
        self.dur_s = 0.0
        self._registry = registry
        self._seconds_total = seconds_total
        self._step_num = step_num

    def set(self, **attrs):
        """Attach attributes mid-span (e.g. the number of nodes reserved)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        # participate in the cluster trace when a context is installed: the
        # thread-local stack gives this span an id + its parent, so nested
        # spans chain causally across every tier for free
        if self._seconds_total is None or _flight.is_open():
            self._span_id, self._parent_id = _tracing.push_span()
        else:
            self._span_id = self._parent_id = None
        self._annotation = _profiler_annotation(self.name, self._step_num)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0_wall = time.time()
        self._t0_mono = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = self.dur_s = time.monotonic() - self._t0_mono
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        _tracing.pop_span(self._span_id)
        ok = exc_type is None
        if self._span_id is not None:
            _tracing.record(
                {
                    "kind": "span",
                    "name": self.name,
                    "trace": _tracing.trace_id(),
                    "span": self._span_id,
                    "parent": self._parent_id,
                    "ts": self._t0_wall,
                    "dur_s": dur,
                    "ok": ok,
                    "tid": threading.get_native_id(),
                    "attrs": dict(self.attrs) if self.attrs else {},
                }
            )
        if self._seconds_total is not None:
            # opened every step: the counter is all the registry keeps
            self._seconds_total.inc(dur)
            return False
        event = {"span": self.name, "ts": self._t0_wall, "dur_s": dur, "ok": ok}
        if self.attrs:
            event.update(self.attrs)
        if self._span_id is not None:
            event["trace"] = _tracing.trace_id()
            event["span_id"] = self._span_id
        self._registry.add_event(event)
        self._registry.histogram(
            self.name + "_seconds", help="duration of {} spans".format(self.name)
        ).observe(dur)
        return False  # never swallow exceptions


def span(name, registry=None, seconds_total=None, step_num=None, **attrs):
    """Open a span::

        with obs.span("reservation_roundtrip", nodes=4):
            ...
        with obs.span("h2d_place", seconds_total=place_seconds):  # every step
            ...

    ``registry`` defaults to the process-global one. Attribute values must be
    JSON-able (they ride the aggregation plane to the driver).
    ``seconds_total`` (a :class:`~tensorflowonspark_tpu.obs.registry.Counter`)
    marks a span that is opened every step: see the module docstring.
    """
    reg = registry if registry is not None else _registry.get_registry()
    if not reg._enabled:
        return _NULL
    return Span(name, reg, attrs, seconds_total, step_num)
