"""The expert layers' grouped matmul against the chip's roofline: the
least time the chip could take for the work of the program runs that
the trace holds with the kernel whole (``kernel_launches``), over the
device time of the kernel's events in those runs. The least time is
the larger of the operations over the bf16 peak and of the bytes over
the HBM peak; the work of one run is the window's mean over the
program's own ``moe.load`` spans (one a launch, written after the fetch:
``rows`` routed and ``experts_hit``, the (expert, layer) pairs that got
any and whose matrices must be read), counted by the model family's
``moe_gmm_flops`` and ``moe_gmm_bytes``, so the share reads the same
work whatever implements it. Parameters: ``patterns``, ``span``.

A program with no such span, or a trace with no such kernel, gives
nothing to read.
"""
from .. import manifest, trace
from . import kernel_launches


def loads(run, span):
    """[(rows, experts_hit)] of the ``span`` events that ended in the
    traced window; read from the trace once and kept on ``run``."""
    found = getattr(run, "moe_loads", None)
    if found is None:
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
                    if "rows" in stats and "experts_hit" in stats and (
                            window.window_s <= 0 or window.start <=
                            ev.end_ns * 1e-9 <= window.end):
                        found.append((int(stats["rows"]),
                                      int(stats["experts_hit"])))
        run.moe_loads = found
    return found


def read(run, params):
    found = loads(run, params["span"])
    if not found:
        return None
    launches, seconds = kernel_launches.held(run, params, len(found),
                                             "launches")
    if not launches or seconds <= 0:
        return None
    family = manifest.plugin("models", run.config["family"])
    model = run.config["model"]
    scale = launches / len(found)
    rows = sum(r for r, _ in found) * scale
    hit = sum(h for _, h in found) * scale
    run.log("{}: {:.0f} rows and {:.0f} (expert, layer) pairs hit a "
            "launch over {} moe.load spans".format(
                params["reader"], rows / launches, hit / launches,
                len(found)))
    least = max(
        family.moe_gmm_flops(model, rows) / run.peaks["bf16_flops_per_s"],
        family.moe_gmm_bytes(model, rows, hit) /
        run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
