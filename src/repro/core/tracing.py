"""In-program tracing of the engine thread: host spans and phase clocks.

Each boundary of the engine thread — an event-loop turn, a scheduler
callback, the front end's arrival poll, a program launch, a device fetch,
a token commit — opens one ``Phases`` phase, which gives two outputs from
the same two clock reads:

  span    a ``jax.profiler.TraceAnnotation`` named after the boundary
          (``engine.turn``, ``backend.launch``, ...).  With a profiler
          running it lands in the trace beside the device events, on the
          same clock, so an idle gap of the device is named by what the
          host was doing in it; with none running it is not made at all
          (``TraceAnnotation.is_enabled``).  The profiler is the only
          switch.
  phase   a cumulative ``time.perf_counter`` clock of *self* time: a
          phase's clock pauses while a phase nested in it runs, so the
          phases of one thread add up to its wall time.

Garbage collection, on any thread, is the span ``host.gc`` and the clock
``host_gc_s``, kept apart from the per-thread sum (a collection pauses the
phase it interrupts without moving that phase's clock).

The backend owns its ``Phases`` (it outlives each run's scheduler) and
reports the clocks through ``stats()``.  ``NO_PHASES``, neither spans nor
clocks, stands in where nothing runs on a device: the simulation-only
path, which imports no JAX (this module imports it only in ``Phases``).
"""
from __future__ import annotations

import gc
import threading
import weakref
from time import perf_counter
from typing import Iterable

# phase clock keys, every one created at construction so that a reader on
# another thread never sees the dict change size
PHASE_KEYS = ("host_loop_s", "host_sched_s", "host_poll_s", "host_launch_s",
              "host_compile_s", "host_fetch_s", "host_commit_s")
GC_KEY = "host_gc_s"


class _Opener:
    """The context manager of one thread's phases: ``Phases.__call__``
    names the phase that opens next, ``__enter__`` opens it, ``__exit__``
    closes the innermost.  One per thread, so opening a phase allocates
    no object of its own."""

    __slots__ = ("seconds", "stack", "next")

    def __init__(self, seconds: dict):
        self.seconds = seconds
        self.stack = []  # open phases, innermost last: [clock, t0, span]
        self.next = None  # (clock, span) of the phase about to open

    def __enter__(self) -> "_Opener":
        key, span = self.next
        if span is not None:
            span.__enter__()
        stack = self.stack
        t = perf_counter()
        if stack:  # pause the enclosing phase
            outer = stack[-1]
            self.seconds[outer[0]] += t - outer[1]
        stack.append([key, t, span])
        return self

    def __exit__(self, *exc) -> bool:
        t = perf_counter()
        stack = self.stack
        key, t0, span = stack.pop()
        self.seconds[key] += t - t0
        if stack:  # resume the enclosing phase
            stack[-1][1] = t
        if span is not None:
            span.__exit__(*exc)
        return False

    def note(self, **meta) -> None:
        """Metadata of the innermost open phase known only at its end (a
        count of what it did); recorded on its span when a profiler
        runs."""
        span = self.stack[-1][2]
        if span is not None:
            span.set_metadata(**meta)


class _Thread(threading.local):
    def __init__(self, seconds: dict):
        self.opener = _Opener(seconds)


class _NoPhase:
    __slots__ = ()

    def __enter__(self) -> "_NoPhase":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **meta) -> None:
        pass


_NO_PHASE = _NoPhase()


class _NoPhases:
    """Neither spans nor clocks: where nothing runs on a device."""

    def __call__(self, span: str, clock: str, /, **meta) -> _NoPhase:
        return _NO_PHASE

    def total(self, keys: Iterable[str]) -> float:
        return 0.0

    def stats(self) -> dict:
        return {}


NO_PHASES = _NoPhases()


class Phases:
    """Spans and exclusive phase clocks of the threads that drive one
    backend.  ``with phases(span, clock, **meta):`` opens a phase:
    ``span`` names the trace event (``meta`` its metadata), ``clock`` the
    key of ``PHASE_KEYS`` its self time goes to.  The call returns the
    calling thread's one opener, so it belongs in a ``with`` statement
    and nowhere else."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self.seconds = dict.fromkeys(PHASE_KEYS + (GC_KEY,), 0.0)
        self._thread = _Thread(self.seconds)
        self._span = TraceAnnotation
        self._tracing = TraceAnnotation.is_enabled
        self._gc_open = None  # (start, span) of the collection under way
        # the collector keeps its callbacks alive: the hook holds this
        # object weakly and leaves with it
        ref = weakref.ref(self)

        def on_gc(phase, info):
            clocks = ref()
            if clocks is not None:
                clocks._on_gc(phase, info)
        gc.callbacks.append(on_gc)
        weakref.finalize(self, gc.callbacks.remove, on_gc)

    def __call__(self, span: str, clock: str, /, **meta) -> _Opener:
        opener = self._thread.opener
        opener.next = (clock, self._span(span, **meta)
                       if self._tracing() else None)
        return opener

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            span = None
            if self._tracing():
                span = self._span("host.gc", generation=info["generation"])
                span.__enter__()
            self._gc_open = (perf_counter(), span)
        elif self._gc_open is not None:
            t0, span = self._gc_open
            self._gc_open = None
            self.seconds[GC_KEY] += perf_counter() - t0
            if span is not None:
                span.__exit__(None, None, None)

    def total(self, keys: Iterable[str]) -> float:
        """Seconds of the given clocks so far (open phases not included)."""
        return sum(self.seconds[k] for k in keys)

    def stats(self) -> dict:
        return dict(self.seconds)
