"""Spans of the compile path: one in-memory recorder for the compiler's own
layers and for JAX's compile events of the modules Daisy builds.

A span is a named interval on one monotonic nanosecond clock, with the id
of the span that encloses it on the same thread and a dict of attributes::

    with span("daisy.compile", program=p.name) as s:
        ...
        s.attrs["cached"] = False

Records go into a buffer that keeps the newest ``MAX_RECORDS``; read them
with ``records()`` and empty it with ``reset()``.  Each span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace of
any run shows the compiler's spans on the device trace's clock.

One ``jax.monitoring`` listener, registered at import, turns JAX's compile
events for Daisy's modules (``daisy_<program>``, see ``module_name``) into
spans: ``jax.trace`` (jaxpr tracing, where the code generator runs),
``jax.lower`` (jaxpr to MLIR) and ``xla.compile`` (the backend compile or
the persistent-cache load), each with attribute ``module``.  ``xla.compile``
carries ``cache``: ``hit`` when the persistent compile cache served the
module, ``miss`` when a cache directory is set and it did not, else
``off``.  JAX's cache events carry no module name; each is charged to the
``xla.compile`` span open on the thread.  Events of other jits are ignored.
"""
from __future__ import annotations

import itertools
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Iterator

import jax

MAX_RECORDS = 1 << 14
MODULE_PREFIX = "daisy_"

# JAX's compile events (``jax/_src/dispatch.py``): each opens with a scalar
# holding its start time and closes with its duration, both with ``fun_name``
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "xla.compile",
}
CACHE_USED = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_MODULE = re.compile(r"(?:jit\()?(" + MODULE_PREFIX + r"\w+)\)?")


@dataclass
class Span:
    """One recorded interval; ``end_ns`` is None while the span is open."""

    id: int
    name: str
    parent: int | None
    start_ns: int
    end_ns: int | None = None
    attrs: dict[str, Any] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        """Length of the closed span."""
        return (self.end_ns - self.start_ns) * 1e-9


class _Open(threading.local):
    """This thread's open spans with their trace annotations, outermost first."""

    def __init__(self):
        self.spans: list[tuple[Span, Any]] = []


_buffer: deque[Span] = deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_open_spans = _Open()


def _open(name: str, attrs: dict[str, Any]) -> Span:
    stack = _open_spans.spans
    s = Span(next(_ids), name, stack[-1][0].id if stack else None,
             time.monotonic_ns(), attrs=attrs)
    annotation = jax.profiler.TraceAnnotation(name, **attrs)
    annotation.__enter__()
    stack.append((s, annotation))
    _buffer.append(s)
    return s


def _close(s: Span) -> None:
    s.end_ns = time.monotonic_ns()
    stack = _open_spans.spans
    for k in range(len(stack) - 1, -1, -1):
        if stack[k][0] is s:
            stack.pop(k)[1].__exit__(None, None, None)
            return


@contextmanager
def span(name: str, **attrs: Any) -> Iterator[Span]:
    """Record the enclosed block as span ``name``; yields the ``Span``, whose
    ``attrs`` may be added to before it closes."""
    s = _open(name, attrs)
    try:
        yield s
    finally:
        _close(s)


def records() -> list[Span]:
    """The buffered spans, in the order they opened."""
    return list(_buffer)


def reset() -> None:
    """Empty the buffer (spans open now still close normally)."""
    _buffer.clear()


def module_name(program_name: str) -> str:
    """The name of the function Daisy builds for a program, and of its JAX
    module: ``heat-3d`` -> ``daisy_heat_3d`` (``jit(daisy_heat_3d)``)."""
    return MODULE_PREFIX + re.sub(r"[^0-9A-Za-z_]", "_", program_name)


def daisy_module(fun_name: str) -> str | None:
    """The Daisy function a JAX ``fun_name`` names (``daisy_x`` or
    ``jit(daisy_x)``), or None for any other function."""
    m = _MODULE.fullmatch(fun_name)
    return m.group(1) if m else None


# -- JAX's compile events ------------------------------------------------------
def _on_scalar(event: str, value: float, **kw: Any) -> None:
    kind = JAX_EVENTS.get(event)
    module = kind and daisy_module(str(kw.get("fun_name", "")))
    if module:
        attrs = {"module": module}
        if kind == "xla.compile":
            attrs["cache"] = "off"
        _open(kind, attrs)


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    kind = JAX_EVENTS.get(event)
    module = kind and daisy_module(str(kw.get("fun_name", "")))
    if module:
        for s, _ in reversed(_open_spans.spans):
            if s.name == kind and s.attrs.get("module") == module:
                _close(s)
                return


def _on_event(event: str, **kw: Any) -> None:
    if event == CACHE_USED:
        outcome = "miss" if jax.config.jax_compilation_cache_dir else None
    else:
        outcome = CACHE_EVENTS.get(event)
    if outcome is None:
        return
    for s, _ in reversed(_open_spans.spans):
        if s.name == "xla.compile":
            s.attrs["cache"] = outcome
            return


jax.monitoring.register_scalar_listener(_on_scalar)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
