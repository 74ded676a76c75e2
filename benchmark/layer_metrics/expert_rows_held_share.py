"""Of the (token, choice) pairs the window's launches routed ANYWHERE,
the share that landed on an expert held here, in percent: ``rows`` over
``routed`` of the program's own ``moe.load`` spans (one a launch; a model
that holds a share of its experts writes both). ``program_spans`` keeps
the ``sched.``, ``engine.`` and ``timer.`` names only, so this is
``moe_gmm_roofline.loads``' pass over the trace with another attribute.
Parameters: ``span``. A program whose spans carry no ``routed`` (it holds
every expert, or is from before the attribute) gives nothing to read."""
from .. import trace


def read(run, params):
    import jax
    data = jax.profiler.ProfileData.from_file(
        trace.find_xplane(run.trace_dir))
    window = run.reduction
    rows = routed = 0
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != params["span"]:
                    continue
                stats = dict(ev.stats)
                if "rows" in stats and "routed" in stats and (
                        window.window_s <= 0 or window.start <=
                        ev.end_ns * 1e-9 <= window.end):
                    rows += int(stats["rows"])
                    routed += int(stats["routed"])
    if not routed:
        return None
    return 100.0 * rows / routed
