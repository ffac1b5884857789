"""Structured tracer: span + counter events, Chrome/Perfetto export.

``Tracer`` records three event kinds into a bounded ring buffer:

* **complete spans** (``ph="X"``): name, monotonic begin, duration,
  thread id, optional args -- ``with tracer.span("flush.build"): ...``
  or, on hot paths that already hold timestamps, the lower-level
  ``tracer.complete(name, t0_ns, dur_ns)``;
* **counter samples** (``ph="C"``): gauge values sampled on transitions
  (immutable-queue depth, compaction debt, compaction-queue depth) --
  Perfetto renders them as stepped counter tracks;
* **instants** (``ph="i"``): point markers.

``tracer.export(path)`` writes Chrome ``trace_event`` JSON that loads
directly in https://ui.perfetto.dev (or chrome://tracing).  Thread ids
are renumbered densely and named via metadata events, so traces diff
cleanly.

**Clock.**  Spans are stamped with ``perf_counter_ns`` (monotonic, and
what the durations come from).  The JAX profiler stamps its host events
with the wall clock (``time.time_ns``).  A tracer reads both clocks
together when it is created and again at export; the export maps every
timestamp onto the profiler's clock through those two readings (offset
and drift), writes it relative to the first event, and records that
origin (profiler-clock ns) and the drift in a ``clock_sync`` metadata
event, so the store's trace can be overlaid on the profiler's.

``watch_jit(tracer)`` adds JAX's own compile steps (``jit.trace``,
``jit.lower``, ``jit.compile``) as spans on the thread that compiled.

``NULL_TRACER`` is the default everywhere: ``enabled`` is False and
every method is a no-op, so untraced runs pay only an attribute check.
"""

from __future__ import annotations

import collections
import json
import threading
import time
from collections.abc import Callable


#: the most two clocks drift apart (500 ppm, the fastest NTP slew)
MAX_DRIFT = 500e-6


class _Span:
    """Context manager recording one complete event on exit."""

    __slots__ = ("_tr", "_name", "_args", "_t0")

    def __init__(self, tr: "Tracer", name: str, args):
        self._tr = tr
        self._name = name
        self._args = args

    def __enter__(self):
        self._t0 = self._tr._clock()
        return self

    def set(self, **args):
        """Add args known only inside the span (``bytes=...``)."""
        self._args = {**(self._args or {}), **args}

    def __exit__(self, exc_type, exc, tb):
        tr = self._tr
        tr._events.append(("X", self._name, self._t0,
                           tr._clock() - self._t0,
                           threading.get_ident(), self._args))
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def set(self, **args):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Bounded in-memory trace recorder (thread-safe: the ring buffer is
    a ``deque`` with atomic appends)."""

    enabled = True

    def __init__(self, maxlen: int = 1_000_000, clock=time.perf_counter_ns,
                 profiler_clock=None):
        """``profiler_clock``: the clock the export maps onto; by default
        ``time.time_ns``, or ``clock`` itself when a custom ``clock`` is
        given (nothing to map a test's clock onto)."""
        self._clock = clock
        if profiler_clock is None:
            profiler_clock = time.time_ns \
                if clock is time.perf_counter_ns else clock
        self._profiler_clock = profiler_clock
        self._events: collections.deque = collections.deque(maxlen=maxlen)
        self._sync0 = self._clock_pair()

    def _clock_pair(self) -> tuple[int, int]:
        """(tracer clock, profiler clock) read together: the tracer
        clock's midpoint around one profiler-clock reading."""
        a = self._clock()
        p = self._profiler_clock()
        return (a + self._clock()) // 2, p

    def now(self) -> int:
        """Current trace clock (ns) -- pair with ``complete``."""
        return self._clock()

    def span(self, name: str, **args) -> _Span:
        """``with tracer.span("flush.build", level=0): ...``"""
        return _Span(self, name, args or None)

    def complete(self, name: str, t0_ns: int, dur_ns: int,
                 args: dict | None = None, tid: int | None = None):
        """Record a finished span from explicit timestamps (hot paths)."""
        self._events.append(
            ("X", name, t0_ns, max(dur_ns, 0),
             threading.get_ident() if tid is None else tid, args))

    def instant(self, name: str, args: dict | None = None):
        self._events.append(
            ("i", name, self._clock(), 0, threading.get_ident(), args))

    def counter(self, name: str, value, args: dict | None = None):
        """Sample a gauge value onto a Perfetto counter track."""
        self._events.append(
            ("C", name, self._clock(), 0, threading.get_ident(),
             {"value": value, **(args or {})}))

    def __len__(self) -> int:
        return len(self._events)

    def clear(self):
        self._events.clear()

    # ------------------------------------------------------------ export

    def to_chrome(self) -> dict:
        """Chrome ``trace_event`` JSON object (Perfetto-loadable).
        Timestamps are on the profiler's clock, in us after the first
        event; the ``clock_sync`` metadata event gives that origin in
        profiler-clock ns."""
        events = list(self._events)
        if not events:
            return {"traceEvents": [], "displayTimeUnit": "ms"}
        (c0, p0), (c1, p1) = self._sync0, self._clock_pair()
        # profiler-clock ns per tracer-clock ns between the two readings;
        # beyond the 500 ppm an NTP slew can reach, the wall clock was
        # stepped: keep the offset, drop the drift
        rate = (p1 - p0) / (c1 - c0) if c1 != c0 else 1.0
        if abs(rate - 1.0) > MAX_DRIFT:
            rate = 1.0
        t0 = min(e[2] for e in events)
        tids: dict[int, int] = {}
        names = {t.ident: t.name for t in threading.enumerate()}
        out = [{"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
                "args": {"name": "repro-lsm"}},
               {"ph": "M", "name": "clock_sync", "pid": 1, "tid": 0,
                "args": {"clock": getattr(self._profiler_clock, "__name__",
                                          "profiler_clock"),
                         "origin_ns": p0 + round((t0 - c0) * rate),
                         "drift_ppm": (rate - 1.0) * 1e6}}]
        meta_at = len(out)
        for ph, name, ts, dur, tid, args in events:
            t = tids.setdefault(tid, len(tids))
            start = round((ts - t0) * rate)
            ev = {"ph": ph, "name": name, "cat": "lsm",
                  "ts": start / 1000.0, "pid": 1, "tid": t}
            if ph == "X":
                ev["dur"] = (round((ts + dur - t0) * rate) - start) / 1000.0
            if ph == "i":
                ev["s"] = "t"
            if args:
                ev["args"] = args
            out.append(ev)
        meta = []
        for ident, t in sorted(tids.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": 1,
                         "tid": t,
                         "args": {"name": names.get(ident, f"thread-{t}")}})
        out[meta_at:meta_at] = meta
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def export(self, path: str):
        """Write the trace as Perfetto-loadable JSON."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)
            f.write("\n")


class NullTracer:
    """Disabled tracer: ``enabled`` is False, every call is a no-op."""

    enabled = False

    def now(self) -> int:
        return 0

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def complete(self, name, t0_ns, dur_ns, args=None, tid=None):
        return None

    def instant(self, name, args=None):
        return None

    def counter(self, name, value, args=None):
        return None

    def __len__(self) -> int:
        return 0

    def clear(self):
        return None

    def to_chrome(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}


NULL_TRACER = NullTracer()


# ---------------------------------------------------------------- jit

#: JAX's compile-time events (``jax.monitoring`` time spans) and the span
#: each becomes.  ``jit.compile`` covers a persistent-cache load too.
JIT_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _JitWatch:
    """The process-wide ``jax.monitoring`` listeners behind ``watch_jit``,
    registered while some tracer holds them.  JAX announces each step as
    it opens (a scalar event carrying its start) and as it closes (a time
    span); both are read on the tracer's clock there and then, so the
    ``jit.*`` spans nest exactly inside the store's spans."""

    def __init__(self):
        self._lock = threading.Lock()
        self._holds: dict[int, list] = {}   # guarded-by: _lock
        self._gen = 0       # written under _lock, read lock-free
        self.targets: tuple = ()    # likewise
        self._local = threading.local()

    def _open(self) -> list:
        """This thread's open steps: ``(event, gen, targets, starts)``."""
        stack = getattr(self._local, "open", None)
        if stack is None:
            stack = self._local.open = []
        return stack

    def on_open(self, event, value, **kw):
        if event in JIT_SPANS and self.targets:
            targets = self.targets
            self._open().append((event, self._gen, targets,
                                 [tr.now() for tr in targets]))

    def on_close(self, event, start_time, end_time, **kw):
        name = JIT_SPANS.get(event)
        if name is None:
            return
        stack = self._open()
        while stack and stack[-1][1] != self._gen:
            stack.pop()     # opened under an earlier registration
        if not stack or stack[-1][0] != event:
            return          # opened before the watch began
        _, _, targets, starts = stack.pop()
        args = {"fun": kw.get("fun_name", "?")}
        if name == "jit.compile":
            # the cache-hit event fires inside the compile step, on its
            # thread
            args["cache_hit"] = getattr(self._local, "hit", False)
            self._local.hit = False
        for tr, t0 in zip(targets, starts):
            tr.complete(name, t0, tr.now() - t0, args)

    def on_event(self, event, **kw):
        if event == _CACHE_HIT:
            self._local.hit = True

    def hold(self, tracer):
        import jax.monitoring as mon
        with self._lock:
            if not self._holds:
                self._gen += 1
                mon.register_scalar_listener(self.on_open)
                mon.register_event_time_span_listener(self.on_close)
                mon.register_event_listener(self.on_event)
            self._holds.setdefault(id(tracer), [tracer, 0])[1] += 1
            self.targets = tuple(t for t, _ in self._holds.values())

    def release(self, tracer):
        import jax.monitoring as mon
        with self._lock:
            entry = self._holds[id(tracer)]
            entry[1] -= 1
            if not entry[1]:
                del self._holds[id(tracer)]
            self.targets = tuple(t for t, _ in self._holds.values())
            if not self._holds:
                self._gen += 1
                mon.unregister_scalar_listener(self.on_open)
                mon.unregister_event_time_span_listener(self.on_close)
                mon.unregister_event_listener(self.on_event)


_JIT = _JitWatch()


def _no_release():
    return None


def watch_jit(tracer) -> Callable[[], None]:
    """Record JAX's trace, lower and compile steps in ``tracer`` as
    ``jit.trace`` / ``jit.lower`` / ``jit.compile`` spans (arg ``fun``;
    ``jit.compile`` also ``cache_hit``) until the returned function is
    called.  Holds are counted per tracer: a tracer that several stores
    share gets each span once.  A disabled tracer registers nothing."""
    if not tracer.enabled:
        return _no_release
    _JIT.hold(tracer)
    held = [True]

    def release():
        if held[0]:
            held[0] = False
            _JIT.release(tracer)
    return release
