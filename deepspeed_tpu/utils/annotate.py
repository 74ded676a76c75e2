"""The program's own boundaries in the PROFILER's trace
(docs/telemetry.md, "Program spans"), where the device's operations lie
on the same clock. A leaf: it imports nothing of this package, so the
timers, the engines and the plan executor can all use it. It has no
recorder, sink or switch of its own.
"""
import contextlib

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # noqa: BLE001 - the timers must work without jax
    _TraceAnnotation = None
_NO_ANNOTATION = contextlib.nullcontext()


def annotate(name, **attrs):
    """Context manager that marks ``name`` (with ``attrs`` as the
    event's statistics) on the calling thread in the profiler's trace.
    Recorded only while a profiler session is active — an operator's
    ``telemetry.trace`` window, or any ``jax.profiler.start_trace`` —
    and otherwise an object made and dropped (under a microsecond).
    Nesting on one thread is the parent link. Pass only values already
    at hand, and keep it out of per-slot and per-token loops."""
    if _TraceAnnotation is None:
        return _NO_ANNOTATION
    return _TraceAnnotation(name, **attrs)
