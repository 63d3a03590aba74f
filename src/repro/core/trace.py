"""Host spans at the program's layer boundaries, on the profiler's clock.

``span("route", n_r=...)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.route``: under a running profiler (``jax.profiler.trace``) it is
one host event on the same clock as the device ops, with its keyword
counts as the event's arguments; with no profiler running it records
nothing and costs well under a microsecond.  There is no switch.

The spans nest: a thread-local stack holds the open spans, ``current()``
names the innermost one, and a span opened inside a span that carries a
request id (``rid``, which ``serve.Router.route`` assigns with
``new_request()``) repeats that id, so every span of one request shares
it.  Device code is labelled with ``jax.named_scope`` instead (HLO
metadata only); the span and scope names are listed in PERF.md §3.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Tuple

from jax.profiler import TraceAnnotation

PREFIX = "repro."

_local = threading.local()
_request_lock = threading.Lock()
_requests = 0


def _stack() -> List[Tuple[str, Optional[int]]]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def current() -> Optional[str]:
    """The innermost open span of this thread (``repro.<name>``), or None."""
    stack = _stack()
    return stack[-1][0] if stack else None


def current_request() -> Optional[int]:
    """The request id the innermost open span carries, or None."""
    stack = _stack()
    return stack[-1][1] if stack else None


def new_request() -> int:
    """A fresh request id (1, 2, ... in this process)."""
    global _requests
    with _request_lock:
        _requests += 1
        return _requests


def last_request() -> int:
    """The last request id ``new_request`` handed out (0: none yet)."""
    return _requests


class span:
    """``with span(name, **counts) as sp:`` — the host span
    ``repro.<name>``; ``sp.add(**counts)`` attaches counts known only at
    its end."""

    def __init__(self, name: str, rid: Optional[int] = None, **counts):
        self.name = PREFIX + name
        self.rid = rid
        self.counts = counts

    def __enter__(self) -> "span":
        stack = _stack()
        if self.rid is None and stack:
            self.rid = stack[-1][1]
        if self.rid is not None:
            self.counts["rid"] = self.rid
        self._annotation = TraceAnnotation(self.name, **self.counts)
        self._annotation.__enter__()
        stack.append((self.name, self.rid))
        return self

    def add(self, **counts) -> None:
        self._annotation.set_metadata(**counts)

    def __exit__(self, *exc) -> None:
        _stack().pop()
        self._annotation.__exit__(*exc)
