"""The gated delta rule's decode kernel's share of its HBM roofline: the
least time the chip's memory could take to read and write, once each,
the float32 state of the slots a decode step ADVANCED, in the decode
steps that the trace holds whole (bytes from shapes over the HBM peak),
over the device time of the kernel's events in those steps
(``kernel_launches``). A step's advanced slots are the window's mean of
what the program's own ``gdn.advanced`` spans say (one a launch:
``slots``, and ``steps`` 1 for a decode step, 0 for a prompt chunk; the
program counts them on the device, the engine fetches the count with
the tokens). ``program_spans`` keeps the ``sched.``, ``engine.`` and
``timer.`` names only, so this is a pass of its own over the trace, as
``expert_rows_held_share``'s is. Parameters: ``patterns``, ``span``. A
program with no such span, or a trace with no such kernel, gives
nothing to read."""
from .. import manifest, trace
from . import kernel_launches


def advanced_slots(run, span):
    """[slots advanced] of the decode steps whose ``span`` ended in the
    traced window."""
    import jax
    data = jax.profiler.ProfileData.from_file(
        trace.find_xplane(run.trace_dir))
    window = run.reduction
    found = []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != span:
                    continue
                stats = dict(ev.stats)
                if int(stats.get("steps", 0)) and "slots" in stats and (
                        window.window_s <= 0 or window.start <=
                        ev.end_ns * 1e-9 <= window.end):
                    found.append(int(stats["slots"]))
    return found


def read(run, params):
    found = advanced_slots(run, params["span"])
    if not found:
        return None
    launches, seconds = kernel_launches.held(run, params, len(found),
                                             "steps")
    if not launches or seconds <= 0:
        return None
    family = manifest.plugin("models", run.config["family"])
    nbytes = family.gated_delta_step_bytes(
        run.config["model"], sum(found) * launches / len(found))
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
