"""Request-scoped trace trees over a contextvar trace context.

``request_trace()`` opens a root span bound to the current async
context; every ``span()`` entered underneath (same task, or any task
spawned while the context is active — ``ensure_future`` copies
contextvars) nests into a tree.  Completed roots land in a bounded
ring buffer retaining the most recent N and the N slowest traces,
served as JSON by ``/debug/traces``.

Trace IDs are 32-hex-char strings.  Inbound HTTP requests adopt a
well-formed ``X-Upow-Trace`` header; outbound gossip RPCs attach the
current ID (see node/peers.py), so one push_tx or block propagation
can be followed across nodes.

Cross-task spans: the intake drainer processes requests submitted
from *other* tasks, so ambient context alone cannot attribute its
per-request work.  ``current_span()`` captured at submit time plus
``child_span()`` / ``add_span()`` / ``attached()`` let the drainer
record against each submitter's tree explicitly.

Every span also feeds the flat ``metrics`` aggregates, so the
pre-existing ``trace.stats()`` consumers see identical numbers.

Every span is also a host event of the JAX profiler: ``request_trace``,
``span`` and the ``child_span``/``finish_child`` pair hold a
``jax.profiler.TraceAnnotation`` open for their duration, so whenever a
profiler session runs (the benchmark's ``--trace 1``, the node's
``/debug/profile``, the miner's SIGUSR1 hook), whichever thread started
it, the program's spans are in the device trace, on the device events'
clock.  The event is built only while a session can hear it (the
class's own ``is_enabled``, one flag read): with none running a span
costs its clock reads and its aggregate.  This module never imports
jax: the class is looked up in ``sys.modules`` once jax is loaded, and
a process that never loads it (wallet, supervisor) pays one dict lookup
a span.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
import sys
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional

from ..logger import get_logger
from . import metrics, scope as _scope

log = get_logger("telemetry")

_TRACE_ID_RE = re.compile(r"^[0-9a-f]{8,64}$")

_current: contextvars.ContextVar[Optional["Span"]] = \
    contextvars.ContextVar("upow_trace_span", default=None)

_LEVELS = {"debug": 10, "info": 20}


#: ``jax.profiler.TraceAnnotation`` once jax is loaded (tests inject a
#: fake here); None until then
_annotation_cls: Any = None
_ANNOTATION_TEXT = 64   # characters of a string field kept in an event


#: ``_annotate``'s trace id for "the ambient root's", looked up only
#: once the event is known to be heard
_AMBIENT = object()


def _annotate(name: str, trace_id: Any, fields: Dict[str, Any]):
    """Open a profiler host event ``name`` carrying the small fields and
    the root's trace id (``trace=<id>``); None when jax is not loaded or
    no profiler session is there to hear it (a class without
    ``is_enabled``, the tests' fake, is always heard).  The profiler
    must never break the caller."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation",
                      None)
        if cls is None:
            return None
        _annotation_cls = cls
    try:
        heard = getattr(cls, "is_enabled", None)
        if heard is not None and not heard():
            return None
        kw = {k: (v[:_ANNOTATION_TEXT] if isinstance(v, str) else v)
              for k, v in fields.items()
              if isinstance(v, (str, int, float)) and k != "name"}
        if trace_id is _AMBIENT:
            parent = _current.get()
            trace_id = parent.root.trace_id if parent is not None else None
        if trace_id:
            kw["trace"] = trace_id
        ann = cls(name, **kw)
        ann.__enter__()
        return ann
    except Exception as e:
        log.debug("profiler annotation %s not opened: %s", name, e)
        return None


def _annotate_end(ann) -> None:
    if ann is not None:
        try:
            ann.__exit__(None, None, None)
        except Exception as e:
            log.debug("profiler annotation not closed: %s", e)


def new_trace_id() -> str:
    return uuid.uuid4().hex


def valid_trace_id(value: Optional[str]) -> bool:
    """True for IDs we are willing to adopt from a peer's header."""
    return bool(value) and _TRACE_ID_RE.match(value) is not None


class Span:
    """One node of a trace tree.

    ``start_ts`` is wall-clock (operator display); durations come from
    ``perf_counter``.  Children are appended at creation; a per-root
    span budget (``max_spans``) stops a pathological request from
    growing its tree without bound — excess spans still feed the flat
    aggregates, they just don't attach.
    """

    __slots__ = ("name", "trace_id", "fields", "start_ts", "_t0",
                 "duration_s", "children", "root", "done", "error", "_ann",
                 "_buf")

    def __init__(self, name: str, trace_id: Optional[str] = None,
                 root: Optional["Span"] = None, **fields: Any):
        self.name = name
        self.trace_id = trace_id
        self.fields = fields
        self.start_ts = time.time()
        self._t0 = time.perf_counter()
        self.duration_s: Optional[float] = None
        self.children: List[Span] = []
        self.root = root if root is not None else self
        self.done = False
        self.error: Optional[str] = None
        self._ann = None        # child_span's open profiler event
        self._buf = None        # open_trace's buffer, pinned for the close

    def finish(self, **fields: Any) -> float:
        if fields:
            self.fields.update(fields)
        if self.duration_s is None:
            self.duration_s = time.perf_counter() - self._t0
        self.done = True
        return self.duration_s

    def to_dict(self) -> dict:
        d: Dict[str, Any] = {
            "name": self.name,
            "start_ts": round(self.start_ts, 6),
            "duration_ms": round((self.duration_s or 0.0) * 1000.0, 3),
        }
        if self.trace_id:
            d["trace_id"] = self.trace_id
        if self.fields:
            d["fields"] = {k: _jsonable(v) for k, v in self.fields.items()}
        if self.error:
            d["error"] = self.error
        if self.children:
            d["spans"] = [c.to_dict() for c in self.children]
        return d


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class TraceBuffer:
    """Bounded retention of completed traces: recent ring + slowest top-N.

    Also tracks the roots currently *in flight* (opened by
    ``request_trace`` but not yet recorded), so a flight recorder can
    snapshot what a node was doing at the moment of a failure."""

    _OPEN_CAP = 256

    def __init__(self, recent: int = 32, slowest: int = 16):
        self._lock = threading.Lock()
        self._open: Dict[int, Span] = {}
        self.configure(recent, slowest)

    def configure(self, recent: int, slowest: int) -> None:
        with self._lock:
            self._recent: deque = deque(maxlen=max(1, int(recent)))
            self._slowest: List[dict] = []
            self._slow_cap = max(1, int(slowest))

    def record_open(self, root: Span) -> None:
        with self._lock:
            if len(self._open) < self._OPEN_CAP:
                self._open[id(root)] = root

    def discard_open(self, root: Span) -> None:
        with self._lock:
            self._open.pop(id(root), None)

    def open_snapshot(self) -> List[dict]:
        """In-flight (not yet recorded) trace roots, oldest first."""
        with self._lock:
            roots = list(self._open.values())
        out = [r.to_dict() for r in roots if not r.done]
        out.sort(key=lambda d: d["start_ts"])
        return out

    def record(self, root: Span) -> None:
        snap = root.to_dict()
        with self._lock:
            self._open.pop(id(root), None)
            self._recent.append(snap)
            self._slowest.append(snap)
            self._slowest.sort(key=lambda t: t["duration_ms"], reverse=True)
            del self._slowest[self._slow_cap:]

    def snapshot(self) -> dict:
        with self._lock:
            return {"recent": list(self._recent),
                    "slowest": list(self._slowest)}

    def reset(self) -> None:
        with self._lock:
            self._recent.clear()
            self._slowest.clear()
            self._open.clear()


_buffer = TraceBuffer()
_max_spans = 512


def _buf() -> TraceBuffer:
    sc = _scope.current()
    return sc.traces if sc is not None else _buffer


def _span_budget() -> int:
    sc = _scope.current()
    return sc.max_trace_spans if sc is not None else _max_spans


def configure(recent: int = 32, slowest: int = 16,
              max_spans: int = 512) -> None:
    global _max_spans
    _max_spans = max(1, int(max_spans))
    _buffer.configure(recent, slowest)


def traces() -> dict:
    return _buf().snapshot()


def open_traces() -> List[dict]:
    """In-flight trace roots of the active scope (or the globals)."""
    return _buf().open_snapshot()


def current_span() -> Optional[Span]:
    return _current.get()


def current_trace_id() -> Optional[str]:
    sp = _current.get()
    return sp.root.trace_id if sp is not None else None


def _attach(parent: Span, child: Span) -> bool:
    root = parent.root
    if root.done:
        return False  # late child of an already-recorded trace
    # per-root span budget lives in the root's field dict (kept out of
    # Span.__slots__; stripped before the tree is recorded)
    used = root.fields.get("_spans", 0)
    if used >= _span_budget():
        return False
    root.fields["_spans"] = used + 1
    parent.children.append(child)
    return True


def open_trace(name: str, trace_id: Optional[str] = None,
               **fields: Any) -> Span:
    """Open a root span that ``close_trace`` ends.  It does not become
    the ambient span (``attached`` does that, for as long as the caller
    works on it): for roots whose lives overlap, as the miner's jobs do
    across a seam."""
    tid = trace_id if valid_trace_id(trace_id) else new_trace_id()
    root = Span(name, trace_id=tid, **fields)
    root._buf = _buf()  # pinned, so that open and record hit one scope
    root._buf.record_open(root)
    root._ann = _annotate(name, tid, fields)
    return root


def close_trace(root: Span, error: Optional[BaseException] = None) -> None:
    """Record ``root``'s tree into the ring buffer it was opened in."""
    if error is not None:
        root.error = type(error).__name__
    _annotate_end(root._ann)
    root._ann = None
    root.finish()
    root.fields.pop("_spans", None)
    metrics.record_span(root.name, root.duration_s)
    root._buf.record(root)


@contextlib.contextmanager
def request_trace(name: str, trace_id: Optional[str] = None,
                  **fields: Any):
    """Open a root span; on exit record the tree into the ring buffer."""
    root = open_trace(name, trace_id, **fields)
    token = _current.set(root)
    try:
        yield root
    except BaseException as e:
        root.error = type(e).__name__
        raise
    finally:
        _current.reset(token)
        close_trace(root)


def span(name: str, level: str = "debug", light: bool = False,
         **fields: Any):
    """Time a section: flat aggregate and profiler event always, tree
    node when traced.  ``light`` is for per-round work (a sweep of 128
    rounds): no tree node is made, so a job's tree and its root's span
    budget are left to the job-level spans, and the span is two clock
    reads and one aggregate update."""
    if light:
        return _LightSpan(name, level, fields)
    return _tree_span(name, level, fields)


def _log_span(name: str, level: str, dt: float,
              fields: Dict[str, Any]) -> None:
    lvl = _LEVELS.get(level, 10)
    if log.isEnabledFor(lvl):
        extra = "".join(f" {k}={v}" for k, v in fields.items())
        log.log(lvl, "%s took %.3fs%s", name, dt, extra)


class _LightSpan:
    """``span(..., light=True)``: yields None, touches no tree."""

    __slots__ = ("_name", "_level", "_fields", "_ann", "_t0")

    def __init__(self, name: str, level: str, fields: Dict[str, Any]):
        self._name, self._level, self._fields = name, level, fields

    def __enter__(self) -> None:
        self._ann = _annotate(self._name, _AMBIENT, self._fields)
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        _annotate_end(self._ann)
        metrics.record_span(self._name, dt)
        _log_span(self._name, self._level, dt, self._fields)
        return False


@contextlib.contextmanager
def _tree_span(name: str, level: str, fields: Dict[str, Any]):
    parent = _current.get()
    node: Optional[Span] = None
    token = None
    if parent is not None and not parent.root.done:
        node = Span(name, root=parent.root, **fields)
        if _attach(parent, node):
            token = _current.set(node)
        else:
            node = None
    ann = _annotate(name, parent.root.trace_id if parent is not None
                    else None, fields)
    t0 = time.perf_counter()
    try:
        yield node
    except BaseException as e:
        if node is not None:
            node.error = type(e).__name__
        raise
    finally:
        dt = time.perf_counter() - t0
        _annotate_end(ann)
        if token is not None:
            _current.reset(token)
        if node is not None:
            node.duration_s = dt
            node.done = True
        metrics.record_span(name, dt)
        _log_span(name, level, dt, fields)


def child_span(parent: Optional[Span], name: str,
               **fields: Any) -> Optional[Span]:
    """Explicitly start a span under ``parent`` (cross-task attribution).

    Returns the started Span (caller must ``finish()`` it) or None when
    there is no parent / the trace is already recorded.  The flat
    aggregate is fed by ``finish_child``.
    """
    if parent is None:
        return None
    node = Span(name, root=parent.root, **fields)
    if not _attach(parent, node):
        return None
    node._ann = _annotate(name, parent.root.trace_id, fields)
    return node


def finish_child(node: Optional[Span], name: Optional[str] = None,
                 **fields: Any) -> None:
    if node is None:
        return
    _annotate_end(node._ann)
    node._ann = None
    dt = node.finish(**fields)
    metrics.record_span(name or node.name, dt)


def add_span(parent: Optional[Span], name: str, t0: float, t1: float,
             **fields: Any) -> None:
    """Attach an already-timed section (perf_counter endpoints) under
    ``parent``.  Used for work shared by many requests (one coalesced
    sig dispatch) that must appear in each requester's tree.  A tree
    node only: an interval that is already over cannot become a
    profiler event, so it is in no device trace."""
    if parent is None:
        return
    node = Span(name, root=parent.root, **fields)
    node.start_ts = time.time() - (time.perf_counter() - t0)
    node.duration_s = max(0.0, t1 - t0)
    node.done = True
    _attach(parent, node)


@contextlib.contextmanager
def attached(sp: Optional[Span]):
    """Make ``sp`` the ambient span for the duration of the block.

    The intake drainer runs in its own context; entering a submitter's
    captured span here routes nested ``span()`` calls — and contextvars
    copied into tasks spawned inside (ws publish, gossip propagate) —
    to that submitter's trace.  A None span is a no-op."""
    if sp is None or sp.root.done:
        yield
        return
    token = _current.set(sp)
    try:
        yield
    finally:
        _current.reset(token)


def reset() -> None:
    _buf().reset()
