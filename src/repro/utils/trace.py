"""Spans and counters of the FL block loop: one in-memory log, and the
profiler's host plane.

``span(name)`` times a block of host code. It enters
``jax.profiler.TraceAnnotation("fl/" + name)``, so under a profiler trace
the span is a host-plane event on the same clock as the device's ops; on
exit it adds the block's seconds to the log (count, total and longest, by
name) and hands them to ``on_done``. ``count(name, n)`` adds to a counter
of the same log. ``snapshot()`` reads the log and ``reset()`` clears it.
The log also keeps its latest ``EVENTS`` entries in order, each span and
each count with its ``time.perf_counter`` times (``events()``), to place
a stall or to cut a window out of a run.

The log is always on: with the profiler off a span costs a few
microseconds. Spans are host code: none sits inside a jitted function.

``watch_host()`` adds two watchers of the host runtime (once, however
often it is called):

* Python's collector (``gc.callbacks``): each collection adds to the
  counters ``gc<generation>`` and ``gc<generation>_s`` (its pause). A
  collection of generation 1 or 2 is also an ``fl/gc`` span; those of
  generation 0 are too frequent to be spans.
* JAX's compiler (``jax.monitoring``): each backend compile (a cache
  load included) counts, with its seconds, under the innermost ``fl/``
  span open on its thread (``""`` outside any), in the snapshot's
  ``compiles``: a recompile inside a run says which step recompiled.

The spans the program emits (``SPANS``), in block order:

=================  ==========================================  ============
span               where                                       layer
=================  ==========================================  ============
``fl/plan``        ``_Planner.plan_schedule``                  planner
``fl/stage``       ``dispatch_block`` before the engine call   data plane
``fl/stage_data``  a store's arena build (staging thread too)  data plane
``fl/pack``        ``FusedEngine._stack_*_schedule``           engine
``fl/put``         ``LocalTrainer.train_schedule``'s upload    data plane
``fl/dispatch``    the compiled block call (enqueue time)      engine
``fl/finish``      ``finish_block``                            data plane
``fl/eval``        ``run_experiment``'s eval fence             harness
``fl/checkpoint``  ``run_experiment``'s checkpoint write       harness
``fl/gc``          a collection of generation 1 or 2           host runtime
=================  ==========================================  ============

Counters: ``plan_draws`` (batch plans drawn), ``h2d_bytes`` (the block
upload's bytes, as ``LocalTrainer.h2d_bytes`` counts them), and the
collector's ``gc0`` .. ``gc2`` / ``gc0_s`` .. ``gc2_s``.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax

PREFIX = "fl/"
SPANS = tuple(PREFIX + n for n in (
    "plan", "stage", "stage_data", "pack", "put", "dispatch", "finish",
    "eval", "checkpoint", "gc"))
EVENTS = 1 << 15        # latest spans and counts kept in order

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.RLock()   # re-entrant: a collection may start (and its
                            # callback log) while this thread holds it
_spans: Dict[str, List[float]] = {}         # name -> [count, seconds, max]
_counters: Dict[str, float] = {}
_compiles: Dict[str, List[float]] = {}      # span -> [count, seconds]
_events: collections.deque = collections.deque(maxlen=EVENTS)
_open = threading.local()                   # this thread's open spans
_watching = False
_gc_start: List = [0.0, None]               # the running collection:
                                            # [start, its TraceAnnotation]


def _stack() -> List[str]:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def _add_span(name: str, start: float, end: float) -> None:
    s = end - start
    with _lock:
        e = _spans.get(name)
        if e is None:
            _spans[name] = [1, s, s]
        else:
            e[0] += 1
            e[1] += s
            if s > e[2]:
                e[2] = s
        _events.append((name, start, end, s))


@contextlib.contextmanager
def span(name: str, on_done: Optional[Callable[[float], None]] = None
         ) -> Iterator[None]:
    """Time the block as the span ``fl/<name>``; ``on_done`` gets its
    seconds. Callers that time device work fence it
    (``jax.block_until_ready``) inside the block: under JAX's async
    dispatch an unfenced span measures the enqueue only."""
    full = PREFIX + name
    stack = _stack()
    with jax.profiler.TraceAnnotation(full):
        stack.append(full)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            stack.pop()
            _add_span(full, t0, t1)
            if on_done is not None:
                on_done(t1 - t0)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    t = time.perf_counter()
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
        _events.append((name, t, t, n))


def snapshot() -> dict:
    """The log: ``spans`` (name -> count, seconds, max_s), ``counters``
    and ``compiles`` (innermost span -> count, seconds)."""
    with _lock:
        return {
            "spans": {n: {"count": int(c), "seconds": s, "max_s": m}
                      for n, (c, s, m) in _spans.items()},
            "counters": dict(_counters),
            "compiles": {n: {"count": int(c), "seconds": s}
                         for n, (c, s) in _compiles.items()},
        }


def events() -> List[Tuple[str, float, float, float]]:
    """The latest ``EVENTS`` entries, oldest first: ``(name, start, end,
    value)`` on the ``time.perf_counter`` clock; a span's value is its
    seconds, a count's (``start == end``) what it added."""
    with _lock:
        return list(_events)


def reset() -> None:
    """Clear the log (spans still open add themselves when they close)."""
    with _lock:
        _spans.clear()
        _counters.clear()
        _compiles.clear()
        _events.clear()


def _on_gc(phase: str, info: dict) -> None:
    gen = info["generation"]
    if phase == "start":
        ann = None
        if gen >= 1:
            ann = jax.profiler.TraceAnnotation(PREFIX + "gc")
            ann.__enter__()
        _gc_start[1] = ann
        _gc_start[0] = time.perf_counter()
        return
    t1 = time.perf_counter()
    t0, ann = _gc_start
    _gc_start[1] = None
    with _lock:
        _counters[f"gc{gen}"] = _counters.get(f"gc{gen}", 0) + 1
        _counters[f"gc{gen}_s"] = _counters.get(f"gc{gen}_s", 0.0) + t1 - t0
    if ann is not None:
        ann.__exit__(None, None, None)
        _add_span(PREFIX + "gc", t0, t1)


def _on_jax_event(event: str, duration: float, **_) -> None:
    if event != _COMPILE_EVENT:
        return
    stack = _stack()
    where = stack[-1] if stack else ""
    with _lock:
        e = _compiles.setdefault(where, [0, 0.0])
        e[0] += 1
        e[1] += duration


def watch_host() -> None:
    """Start the collector and compiler watchers (once per process)."""
    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    gc.callbacks.append(_on_gc)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
