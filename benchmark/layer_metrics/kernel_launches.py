"""The runs of a program that a traced window holds with a kernel's
events whole (``trace.Reduction.whole_launches``), beside the steps
the host counted. Not a reader: the roofline readers share it.

A roofline share divides the least time of some WORK by the device time
of the kernel's EVENTS. The host counts the work (steps, live slots,
live pages, chunk tokens) and the trace holds the events, and the two
need not cover the same steps: a trace can lose operations, or start
late, and a share worked out from all the host's work over the events
that are left then passes 100% (the check of PR 32 read 142.8% for
``mamba_step_roofline`` in one run; section 7 of PERF.md). So a reader
takes from the host only the work of ONE run of the program that holds
the kernel, as a mean (the host's count over the host's steps, one run
a step), and prices as many runs as the trace holds whole, over the
time of the kernel's events inside those runs."""


def held(run, params, units, unit):
    """-> (whole runs, seconds of the kernel inside them), or (0, 0.0)
    where the trace holds none; logs them beside the host's ``units``
    (how many ``unit`` it counted: one run of the program each)."""
    whole, part, seconds = run.reduction.whole_launches(params["patterns"])
    if not whole:
        return 0, 0.0
    run.log("{}: the trace holds {:g} program runs with the kernel whole "
            "and {:g} in part ({:.6f} s of the kernel); the host counted "
            "{} {}".format(params["reader"], whole, part, seconds, units,
                           unit))
    return whole, seconds
