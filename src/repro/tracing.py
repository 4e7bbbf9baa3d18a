"""Host spans of the program, on the profiler's clock.

``with span(name, **args):`` marks a region with
``jax.profiler.TraceAnnotation``, so a profiler trace holds it on its host
plane, beside the device's ops and on the same clock. While ``record(True)``
is in force, each span is also kept in memory as ``[name, parent, start,
end]``: the parent is the record of the span open around it on the same
thread, and the times are ``time.perf_counter`` seconds. ``totals`` sums the
kept spans by name.

Recording is off by default. A span then keeps nothing: it enters its
annotation and checks one flag.
"""

from __future__ import annotations

import collections
import threading
import time

import jax

_on = False
_records: list[list] = []
_local = threading.local()


def _stack() -> list:
    """The records of this thread's open spans, innermost last."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager: a named host span, with ``args`` as its arguments in
    the profiler trace."""

    __slots__ = ("_note", "_name", "_rec")

    def __init__(self, name: str, /, **args):
        self._note = jax.profiler.TraceAnnotation(name, **args)
        self._name = name
        self._rec = None

    def __enter__(self):
        self._note.__enter__()
        if _on:
            stack = _stack()
            self._rec = [self._name, stack[-1] if stack else None, time.perf_counter(), None]
            _records.append(self._rec)
            stack.append(self._rec)
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            self._rec[3] = time.perf_counter()
            _stack().pop()
        return self._note.__exit__(*exc)


def record(on: bool) -> None:
    """Keep the spans entered from now on in memory (``True``), or stop."""
    global _on
    _on = bool(on)


def reset() -> None:
    """Forget the spans kept so far."""
    global _records
    _records = []


def records() -> list[tuple[str, int | None, float, float]]:
    """The closed spans kept, in the order they were entered, as ``(name,
    parent, start, end)``; ``parent`` is the index of the parent's entry in
    this list, or None."""
    done = [r for r in _records if r[3] is not None]
    index = {id(r): i for i, r in enumerate(done)}
    return [(n, index.get(id(p)), a, b) for n, p, a, b in done]


def totals() -> dict[str, dict]:
    """Per span name: ``n`` spans closed, ``s`` their seconds, and ``self_s``
    those seconds less the time their child spans cover."""
    done = [r for r in _records if r[3] is not None]
    child_s: dict[int, float] = collections.defaultdict(float)
    for _, parent, a, b in done:
        if parent is not None:
            child_s[id(parent)] += b - a
    out: dict[str, dict] = {}
    for r in done:
        t = out.setdefault(r[0], {"n": 0, "s": 0.0, "self_s": 0.0})
        t["n"] += 1
        t["s"] += r[3] - r[2]
        t["self_s"] += r[3] - r[2] - child_s[id(r)]
    return out
