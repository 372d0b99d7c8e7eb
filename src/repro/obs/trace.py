"""Per-request trace spans, live on the wall clock and on the profiler's.

A `Span` is one timed region with attributes and children.  It is
opened around the work it times, as a context manager: ``t_wall`` is
its start on the `time.time` clock, ``dur_s`` its wall duration and
``cpu_s`` the CPU time its thread used inside it (`time.thread_time`).
A child lies inside its parent's interval; siblings may overlap.

`read_batch` opens a ``read`` root per `ReadSpec`, with ``plan``,
``fetch``, ``decode`` and ``admit`` children.  Batch execution is
*phase-ordered across requests* (all plans, then all fetches, ...), so
that code attaches children to an explicit parent (`Tracer.span` takes
``parent=``).  Code deeper down — the executor, `_BatchIO`, the codec —
opens a child of whatever span is current in its context with
`Tracer.stage`, without a parameter threaded through every call; where
no span is current (telemetry off, or a codec call outside a read) a
stage builds nothing.

While a span is open it also holds a ``jax.profiler.TraceAnnotation``
named ``vss.<name>`` that carries its root's ``read_id``, so a profiler
trace shows the program's stages on the same clock as the device's
operations.  JAX is imported on the first span, so this module imports
without it.

Finished roots land in a fixed-size deque; `Tracer.recent()` returns
them oldest-first as plain dicts, and `export_jsonl` renders the JSON
lines form `VSS.recent_traces()` documents."""

from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import json
import threading
import time
from collections import deque
from typing import Callable, ContextManager, Dict, List, Optional

DEFAULT_TRACE_CAPACITY = 256
ANNOTATION_PREFIX = "vss."

# perf_counter_ns + this = time.time_ns: one clock for a span's start and
# its duration, so a child's interval never pokes out of its parent's
_WALL_OFFSET_NS = time.time_ns() - time.perf_counter_ns()
_ROOT_IDS = itertools.count(1)


@functools.cache
def _annotation():
    """The profiler's host annotation, or a stand-in without JAX."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover - environment-dependent
        return lambda name, **metadata: contextlib.nullcontext()
    return TraceAnnotation


class Span:
    """One timed region.  Building a span starts it (and its profiler
    annotation); `finish` ends both."""

    __slots__ = ("name", "t_wall", "dur_s", "cpu_s", "attrs", "children",
                 "read_id", "_ns0", "_cpu0", "_ann")

    def __init__(self, name: str, parent: Optional["Span"] = None, **attrs):
        self.name = name
        self.attrs: Dict[str, object] = attrs
        self.children: List["Span"] = []
        self.read_id = parent.read_id if parent is not None else next(_ROOT_IDS)
        self.dur_s = self.cpu_s = 0.0
        self._ann = _annotation()(ANNOTATION_PREFIX + name,
                                  read_id=self.read_id)
        self._ann.__enter__()
        self._ns0 = time.perf_counter_ns()  # the wall interval holds the
        self._cpu0 = time.thread_time()     # CPU one
        self.t_wall = (self._ns0 + _WALL_OFFSET_NS) / 1e9

    def finish(self) -> "Span":
        self.cpu_s = time.thread_time() - self._cpu0
        self.dur_s = (time.perf_counter_ns() - self._ns0) / 1e9
        self._ann.__exit__(None, None, None)
        return self

    def twin(self, **attrs) -> "Span":
        """The same finished interval with other attributes: how one
        stage shared by several requests (a group's plan or fetch) is
        recorded on each of them."""
        sp = object.__new__(Span)
        sp.name, sp.attrs, sp.children = self.name, attrs, []
        sp.read_id, sp.t_wall = self.read_id, self.t_wall
        sp.dur_s, sp.cpu_s = self.dur_s, self.cpu_s
        return sp

    def to_dict(self, root: bool = True) -> Dict:
        """Plain dict; a root's also carries its ``read_id``."""
        d: Dict[str, object] = {
            "name": self.name,
            "t_wall": self.t_wall,
            "dur_s": self.dur_s,
            "cpu_s": self.cpu_s,
        }
        if root:
            d["read_id"] = self.read_id
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict(False) for c in self.children]
        return d


class Tracer:
    """Bounded retention of finished root spans.

    ``enabled=False`` builds nothing: `span` yields None and records
    nothing, and no stage below it finds a current span."""

    # the innermost open span of this thread or task, whatever tracer
    # opened it: what `stage` attaches to
    _current: contextvars.ContextVar = contextvars.ContextVar(
        "repro_obs_current_span", default=None)

    def __init__(self, capacity: int = DEFAULT_TRACE_CAPACITY,
                 enabled: bool = True):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(capacity)))

    def record(self, root: Span) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._ring.append(root)

    def span(self, name: str, parent: Optional[Span] = None,
             **attrs) -> ContextManager[Optional[Span]]:
        """Timed region, current while open; a child of ``parent``, or
        a root (with a new ``read_id``) recorded when it ends without an
        error.  Yields None when the tracer is disabled."""
        if not self.enabled:
            return _NOTHING
        return _Open(name, parent, attrs,
                     self.record if parent is None else None)

    @staticmethod
    def stage(name: str, **attrs) -> ContextManager[Optional[Span]]:
        """Timed child of the current span, current while open; yields
        None, and builds nothing, where no span is current."""
        parent = Tracer._current.get()
        if parent is None:
            return _NOTHING
        return _Open(name, parent, attrs, None)

    def recent(self, n: Optional[int] = None) -> List[Dict]:
        """Oldest-first dicts of the last ``n`` (default: all retained)
        root spans."""
        with self._lock:
            roots = list(self._ring)
        if n is not None:
            roots = roots[-int(n):]
        return [r.to_dict() for r in roots]

    def export_jsonl(self, n: Optional[int] = None) -> str:
        """One JSON document per retained root span, newline-separated."""
        return "\n".join(
            json.dumps(d, default=str) for d in self.recent(n)
        )

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


_NOTHING = contextlib.nullcontext()  # yields None; holds no state


class _Open:
    """Opens one span on entry, current until exit, which ends it and
    hands a root to ``record``."""

    __slots__ = ("name", "parent", "attrs", "record", "span", "token")

    def __init__(self, name: str, parent: Optional[Span], attrs: Dict,
                 record: Optional[Callable[[Span], None]]):
        self.name, self.parent, self.attrs = name, parent, attrs
        self.record = record

    def __enter__(self) -> Span:
        sp = self.span = Span(self.name, self.parent, **self.attrs)
        if self.parent is not None:
            self.parent.children.append(sp)
        self.token = Tracer._current.set(sp)
        return sp

    def __exit__(self, exc_type, exc, tb) -> None:
        Tracer._current.reset(self.token)
        self.span.finish()
        if self.record is not None and exc_type is None:
            self.record(self.span)
