"""Host spans of the served path, written through the profiler.

Every span is a ``jax.profiler.TraceAnnotation``: it costs about a
microsecond when no trace is active, and under ``jax.profiler.trace``
the profiler records it on the ``/host:CPU`` plane, on the same clock as
the device planes, so a trace shows what the host was doing while the
device sat idle.

One call of the served entry (``scheduler.stream_search``) writes one
``search.call`` span, tiled by consecutive children: ``search.setup``
(holding ``search.warmup``), then for each round-chunk dispatch
``search.stage``, ``search.dispatch``, ``search.sync``, with a tiered
page store ``search.pages`` (its chunk boundary: the bitmap and pool
transfer, ``PageStore.boundary`` and the consts refresh), and
``search.account``, then ``search.finish``. Every span carries the
call's ``call`` id; the per-dispatch spans also carry ``chunk``, the
dispatch's index within the call. The ids ride in the event's stats,
not its name.
"""
from __future__ import annotations

import itertools

import jax

CALL = "search.call"
SETUP = "search.setup"
WARMUP = "search.warmup"
STAGE = "search.stage"
DISPATCH = "search.dispatch"
SYNC = "search.sync"
PAGES = "search.pages"
ACCOUNT = "search.account"
FINISH = "search.finish"

_calls = itertools.count()


def next_call() -> int:
    """A process-wide id for the next call of the served entry."""
    return next(_calls)


def span(name: str, **ids):
    return jax.profiler.TraceAnnotation(name, **ids)


class Phases:
    """Consecutive child spans of one call, each ending where the next
    begins, so together they leave no host work of the call outside a
    span. ``phases(name, **ids)`` ends the open span and opens ``name``;
    the same name and ids again keep the open one. Leaving the ``with``
    block ends the last."""

    def __init__(self, call: int):
        self.call = call
        self._key = None
        self._open = None

    def __call__(self, name: str, **ids) -> None:
        key = (name, tuple(sorted(ids.items())))
        if key == self._key:
            return
        self.end()
        self._key = key
        self._open = span(name, call=self.call, **ids)
        self._open.__enter__()

    def end(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = self._key = None

    def __enter__(self) -> "Phases":
        return self

    def __exit__(self, *exc) -> None:
        self.end()
