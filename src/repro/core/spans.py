"""Program spans: named intervals of the characterize path, and of the
serving engine's cache placement, on the profiler's host trace.

Each span is a ``jax.profiler.TraceAnnotation`` named ``memscope.<name>``.
While a profiler runs, it lands in the host plane on the same clock as
the device planes, so an interval in which the device sat idle can be
put down to what the program was doing; with no profiler running a
span costs about a microsecond and records nothing.  The profiler keeps
the spans and writes them out: there is no store here.

The spans are flat: none of them is opened inside another, so each
instant of the host's time lies under at most one.  A span opened
inside :func:`measurement` carries that measurement's identity
(strategy, bytes, members, group) as its arguments, so the spans of one
measurement share an identifier.

=========  ==================================================
span       covers
=========  ==================================================
plan       grouping the sweep's observers into measurements
inputs     building, placing and freeing a measurement's operands
build      a program's first call: trace, lower, compile or cache
           load, first run
timed      the timed samples whose median becomes ``elapsed_ns``
readback   checksums brought to the host
assemble   assembling runs, the queueing-model solves included
curvedb    turning a matrix result into a CurveDB
place      a ``generate`` call's cache advice and placement
           (arguments ``kv_bytes``, ``state_bytes``, ``kv_pool``,
           ``state_pool``)
=========  ==================================================
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Iterator, Union

import jax

PREFIX = "memscope."
NAMES = ("plan", "inputs", "build", "timed", "readback", "assemble",
         "curvedb", "place")

Arg = Union[str, int]
_IDENTITY: contextvars.ContextVar[Dict[str, Arg]] = contextvars.ContextVar(
    "memscope_measurement", default={})


def span(name: str, **args: Arg) -> jax.profiler.TraceAnnotation:
    """The span ``memscope.<name>``, carrying the current measurement's
    identity and ``args``; enter it with ``with``."""
    if name not in NAMES:
        raise KeyError(f"unknown span {name!r}; have {NAMES}")
    return jax.profiler.TraceAnnotation(PREFIX + name,
                                        **{**_IDENTITY.get(), **args})


@contextlib.contextmanager
def measurement(**identity: Arg) -> Iterator[None]:
    """Give every span opened inside this block ``identity`` as
    arguments."""
    token = _IDENTITY.set(identity)
    try:
        yield
    finally:
        _IDENTITY.reset(token)
