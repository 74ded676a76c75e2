"""Continuous-batching scheduler step, lowered onto the segment
executor.

Replaces the bespoke phase sequence that lived in
``ContinuousBatchingScheduler._step_impl``: one scheduler step is now
a :class:`~.plan.SegmentPlan` —

  ``admit -> prefill -> decode -> retire``

where ``admit`` fills free slots from the queue (paged admission,
prefix-cache mapping), ``prefill`` runs at most one prefill chunk per
admitted-but-not-ready slot, ``decode`` runs one fused decode/verify
step for every decoding slot, and ``retire`` closes the step (step
counters, occupancy accounting, the serving_step telemetry record)
and carries the retired uids out as the plan's kept result.

Serving-phase state rides the scheduler object (slots, queue, the
``retired`` list) rather than the value environment — the deps encode
the ORDER contract (a decode may never observe a half-admitted slot),
which is what the executor enforces and the auditor fingerprints.
Every segment is main-thread synchronous: the serving step is a strict
phase chain (each phase reads slot state the previous one wrote), so
serial and overlap modes execute identically by construction — the
lowering buys the plan REPRESENTATION (pricing, auditing, rewrite
passes over multi-plan programs), not intra-step overlap.

``_serving_step_topology`` is the ONE place the plan shape is written
down: ``build_serving_plan(engine_or_scheduler)`` with no payloads is
the ABSTRACT twin for ``analysis.ir.plan_of`` / the auditor.

In the profiler's trace (utils.annotate) each segment is one
``sched.<name>`` span, and ``sched.plan`` is what comes before the
first: this module's plan build and the executor's validation and
set-up. What lies between the segments' spans is the executor's own
bookkeeping, the self time of the enclosing ``sched.step``.
"""
import contextlib

from ...utils.annotate import annotate
from .plan import Segment, SegmentPlan


def _serving_step_topology():
    """Ordered (name, kind, deps, pool, phase) descriptors of one
    continuous-batching scheduler step."""
    return [
        ("admit", "host", (), None, None),
        ("prefill", "compute", ("admit",), None, "prefill_s"),
        ("decode", "compute", ("prefill",), None, "decode_s"),
        ("retire", "host", ("decode",), None, None),
    ]


def build_serving_plan(engine_or_scheduler=None, payloads=None):
    """Segment plan of one scheduler step. ``payloads`` maps names to
    run callables; absent -> abstract plan (``ir.plan_of``). The plan
    shape is state-independent, so the engine/scheduler argument is
    accepted only for signature symmetry with the other builders."""
    payloads = payloads or {}
    plan = SegmentPlan("serving_step")
    for name, kind, deps, pool, phase in _serving_step_topology():
        plan.add(Segment(
            name=name, kind=kind, deps=deps,
            run=payloads.get(name),
            async_ok=pool is not None, pool=pool or "d2h", phase=phase,
            keep_result=(name == "retire")))
    return plan


def run_serving_step(sched, record_step):
    """One scheduler step on the executor. Returns the retired uids —
    bit-exact with the bespoke phase sequence (same phase callables in
    the same order; the plan adds ordering enforcement, per-segment
    accounting and the audit/rewrite surface)."""
    retired = []
    state = {}
    setup = contextlib.ExitStack()

    def admit(env):
        setup.close()           # the first segment runs: set-up is over
        with annotate("sched.admit"):
            sched._admit()

    def prefill(env):
        with annotate("sched.prefill"):
            sched._prefill_chunks(retired)
        # occupancy counts slots that did work THIS step — retire-at-
        # prefill already freed some, so measure before the decode
        # retire pass too
        state["busy"] = sched.num_active + len(retired)

    def decode(env):
        with annotate("sched.decode"):
            sched._decode(retired)

    def retire(env):
        with annotate("sched.retire"):
            engine = sched.engine
            sched.steps += 1
            engine.serving_record_steps = record_step + 1
            occupancy = min(state["busy"], engine.num_slots) \
                / engine.num_slots
            sched._account("record_schedule",
                           occupancy=occupancy,
                           queue_depth=len(sched.queue), step=sched.steps)
            pool = getattr(engine, "state", None)
            if pool is not None:
                sched._account("record_state_pool", pool.num_slots,
                               pool.nbytes, sched.num_active)
            tel = getattr(engine, "telemetry", None)
            if tel is not None:
                # one serving_step record per scheduler step through the
                # same sink layer the training engine writes
                tel.emit_serving_step(
                    step=record_step, metrics=sched._record_metrics,
                    active_slots=sched.num_active,
                    queue_depth=len(sched.queue), occupancy=occupancy,
                    page_pool=engine.page_pool_stats(),
                    prefix=engine.prefix_stats(),
                    role=getattr(engine, "serving_role", None))
            return retired

    payloads = {"admit": admit, "prefill": prefill, "decode": decode,
                "retire": retire}
    with setup:
        setup.enter_context(annotate("sched.plan"))
        plan = build_serving_plan(sched.engine, payloads=payloads)
        env = sched.engine.plan_executor().execute(plan)
    return env["retire"]
