"""Host spans of the serving loop, on the profiler's clock.

``span(name, **ids)`` marks one step of the program (``serve.round``,
``serve.round.logits``, ``serve.idle`` ...). With tracing off, the
default, it returns one shared no-op context manager: no object is
built and JAX is not called. With tracing on it returns
``jax.profiler.TraceAnnotation(name, **ids)``, which writes the span
onto the profiler's host plane, on the same clock as the device's
"XLA Ops" and "XLA Modules" lines, so a device idle gap can be put
down to the step the host was in. The ``ids`` become the event's
stats; its name stays the bare ``name``.

An operator captures a trace with::

    tracing.enable(True)
    with jax.profiler.trace(log_dir):
        ...                      # serve for a few seconds

Tracing, like the profiler it feeds, is one switch for the process.

The engine's counters stay on whatever this switch says; for an MoE
config they include the held-expert layer's routing, summed over its
layers and fetched with each launch's logits: ``moe_routed_pairs``,
``moe_held_pairs`` and ``moe_experts_touched`` (DESIGN.md §17). Its
kernel shows in the device trace as ``moe_experts``.
"""
from __future__ import annotations

from contextlib import nullcontext

import jax.profiler

_NOOP = nullcontext()
_on = False


def enable(on: bool = True) -> None:
    """Turn the program's spans on or off for the whole process."""
    global _on
    _on = bool(on)


def span(name: str, **ids):
    """A context manager around one step of the serving loop."""
    if not _on:
        return _NOOP
    return jax.profiler.TraceAnnotation(name, **ids)
