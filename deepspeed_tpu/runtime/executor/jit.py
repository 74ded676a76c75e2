"""The one place a step program declares donation.

Every jitted step program in the repo obtains its wrapper through
:func:`jit_program` (directly, or via ``DeepSpeedEngine._get_jit`` /
``StreamedOffloadRunner._jit`` which route here): the executor owns the
donation policy exactly like it owns async dispatch and phase timing
(DSL006 — step scheduling lives in ``runtime/executor/`` only; since
ISSUE 19 the baseline for that rule is EMPTY).

``donate`` is the same declaration :class:`~.plan.Segment.donate`
mirrors and ``analysis/rules.py``'s donation audit reads — one spelling
per program, checked end to end: the engine passes it here, the plan
records it, the auditor verifies the jitted program honors it.

It is also where a program just made enters the start-up record
(:func:`first_call`, docs/telemetry.md "Start-up record"): the row is
opened here and closed by the engine once the first call is over;
nothing wraps the call, and the engine's cache holds the jitted
function itself from the start.
"""
import jax

from ...utils.compile_cache import close_program_row, open_program_row


def jit_program(fn, donate=(), **jit_kwargs):
    """``jax.jit`` with the executor-owned donation declaration.

    ``donate``: positional argnums the program consumes (its
    ``donate_argnums``). Extra ``jit_kwargs`` (``out_shardings``,
    ``static_argnums``, ...) pass through untouched.
    """
    if donate:
        jit_kwargs["donate_argnums"] = tuple(donate)
    return jax.jit(fn, **jit_kwargs)


def first_call(fn, program, key, engine, step):
    """Open the ``setup.program`` row [``program``, ``key``: the
    engine's cache key, ``engine``: its tag, ``step``: its count of
    launches or steps now] of ``fn``, which :func:`jit_program` has just
    returned and the engine is about to call for the first time. ->
    what :func:`first_call_over` closes it with."""
    return open_program_row(
        program, key, engine, int(step),
        getattr(getattr(fn, "__wrapped__", None), "__name__", None), fn)


first_call_over = close_program_row
