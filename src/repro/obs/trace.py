"""Span tracer (stdlib-only): nested wall-clock spans → trace.jsonl →
Chrome/Perfetto ``trace_event`` JSON.

A span is one timed region with attributes::

    tracer = get_tracer()
    with tracer.span("loop/step", step=step) as sp:
        ...
        sp.set(refresh_groups=2)      # attrs discovered mid-span

Rows are appended to an append-only ``trace.jsonl`` on span EXIT (one
JSON object per line — the same torn-write-tolerant journal format as
``events.jsonl``), with nesting recovered from per-thread ``parent``/
``depth`` fields rather than file order, so interleaved threads and
worker restarts append safely to one file.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
once the process has imported jax (this module never imports it), with
or without a path: a profiler session then shows the span as a host
event on the profiler's clock, beside the device's operations. With no
profiler session and no path a span costs about 1 µs (the annotation's
construction; no row, no clock read); before jax is imported ``span()``
returns a shared no-op context manager. That is what the
``benchmarks/overhead.run_obs`` <3% hot-path gate certifies.

Export: :func:`export_perfetto` converts a trace.jsonl into the Chrome
``trace_event`` format (``{"traceEvents": [...]}``; ``ph: "X"`` complete
events with microsecond ``ts``/``dur``, ``ph: "i"`` instants) which
chrome://tracing and https://ui.perfetto.dev load directly.

Workers configure the module tracer once at boot (``configure(path)`` —
``launch/worker.py`` does this from ``ElasticConfig.trace_path``); the
``REPRO_TRACE`` environment variable is the no-code-change override.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional


class _NullSpan:
    """Shared no-op span: the entire disabled-tracing code path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        return None


_NULL_SPAN = _NullSpan()

_ANNOTATION = None  # set by _annotation() once jax is imported


def _annotation():
    """The class of a profiler-only span: ``jax.profiler.TraceAnnotation``
    with the span's ``set``. None while the process has not imported jax."""
    global _ANNOTATION
    if _ANNOTATION is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None

        class _Annotation(profiler.TraceAnnotation):
            __slots__ = ()

            def set(self, **attrs) -> None:
                return None

        _ANNOTATION = _Annotation
    return _ANNOTATION


class _Span:
    __slots__ = ("tracer", "name", "attrs", "t0", "parent", "depth", "annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any],
                 annotation=None):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.annotation = annotation

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        stack = self.tracer._stack()
        self.parent = stack[-1].name if stack else None
        self.depth = len(stack)
        stack.append(self)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.time() - self.t0
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        row = {
            "ph": "X",
            "name": self.name,
            "ts": self.t0,
            "dur": dur,
            "pid": self.tracer.pid,
            "tid": threading.get_ident(),
            "host": self.tracer.host,
            "depth": self.depth,
            "parent": self.parent,
        }
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        if self.attrs:
            row["attrs"] = self.attrs
        self.tracer._write(row)


class Tracer:
    """Appends span/instant rows to one jsonl file; thread-safe (a lock
    serializes writes, a ``threading.local`` stack tracks nesting per
    thread). With ``path=None`` the tracer writes nothing and its spans
    are profiler annotations alone."""

    def __init__(self, path: Optional[str] = None,
                 host: Optional[str] = None):
        self.path = path
        self.host = host or os.environ.get("REPRO_HOST_ID", "")
        self.pid = os.getpid()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._f = None
        if path:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._f = open(path, "a")

    @property
    def enabled(self) -> bool:
        return self._f is not None

    def _stack(self) -> List[_Span]:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def _write(self, row: Dict[str, Any]) -> None:
        if self._f is None:
            return
        line = json.dumps(row, default=str)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    # -- the API -------------------------------------------------------------
    def span(self, name: str, **attrs):
        """A timed context manager; the row is written on exit. It is also
        a profiler annotation of ``name`` once jax is imported."""
        annotation = _ANNOTATION or _annotation()
        if self._f is None:
            return _NULL_SPAN if annotation is None else annotation(name)
        return _Span(self, name, attrs,
                     None if annotation is None else annotation(name))

    def instant(self, name: str, **attrs) -> None:
        """A point event (``ph: "i"``) — decisions, faults, commits."""
        if self._f is None:
            return
        row = {
            "ph": "i",
            "name": name,
            "ts": time.time(),
            "pid": self.pid,
            "tid": threading.get_ident(),
            "host": self.host,
        }
        if attrs:
            row["attrs"] = attrs
        self._write(row)

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


_TRACER = Tracer(os.environ.get("REPRO_TRACE") or None)


def get_tracer() -> Tracer:
    """THE process-wide tracer (disabled unless configured)."""
    return _TRACER


def configure(path: Optional[str], host: Optional[str] = None) -> Tracer:
    """(Re)configure the process tracer — what a worker does at boot from
    ``ElasticConfig.trace_path``. ``path=None`` disables. Idempotent: a
    reconfigure to the same path keeps appending to it."""
    global _TRACER
    if _TRACER.path == path and (host is None or _TRACER.host == host):
        return _TRACER
    old = _TRACER
    _TRACER = Tracer(path, host=host)
    old.close()
    return _TRACER


# -- reading / export --------------------------------------------------------


def read_trace(path: str) -> List[Dict[str, Any]]:
    """All well-formed rows of a trace.jsonl (torn trailing lines — a
    killed worker mid-append — are skipped, like ``read_events``)."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(row, dict) and "name" in row and "ts" in row:
                    out.append(row)
    except FileNotFoundError:
        pass
    return out


def trace_events(rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Chrome ``trace_event`` list from trace rows: ``ph "X"`` complete
    events (ts/dur in µs) and ``ph "i"`` instants. One process row per
    (host, pid) via ``process_name`` metadata."""
    events: List[Dict[str, Any]] = []
    seen_procs = set()
    for r in rows:
        pid = int(r.get("pid", 0))
        host = r.get("host") or ""
        if (host, pid) not in seen_procs:
            seen_procs.add((host, pid))
            events.append({
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": f"{host or 'proc'}:{pid}"},
            })
        ev = {
            "name": r["name"],
            "ph": r.get("ph", "X"),
            "ts": float(r["ts"]) * 1e6,
            "pid": pid,
            "tid": int(r.get("tid", 0)),
            "cat": str(r["name"]).split("/")[0],
            "args": dict(r.get("attrs") or {}),
        }
        if ev["ph"] == "X":
            ev["dur"] = float(r.get("dur", 0.0)) * 1e6
        else:
            ev["s"] = "t"  # instant scope: thread
        events.append(ev)
    return events


def export_perfetto(trace_path: str, out_path: str) -> Dict[str, Any]:
    """trace.jsonl → a Perfetto/chrome://tracing-loadable JSON file.
    Returns the document (also written to ``out_path``)."""
    doc = {
        "traceEvents": trace_events(read_trace(trace_path)),
        "displayTimeUnit": "ms",
    }
    parent = os.path.dirname(out_path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = f"{out_path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out_path)
    return doc
