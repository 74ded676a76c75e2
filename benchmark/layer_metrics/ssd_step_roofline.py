"""Mamba-2's decode kernel's share of its HBM roofline: the least time
the chip's memory could take to read and write, once each, the float32
state of the slots a decode step ADVANCED, in the decode steps that the
trace holds whole (bytes from shapes over the HBM peak), over the device
time of the kernel's events in those steps (``kernel_launches``). A
step's advanced slots are the window's mean of what the program's own
``ssd.advanced`` spans say (one a launch: ``slots``, and ``steps`` 1 for
a decode step, 0 for a prompt chunk; the program counts them on the
device, the engine fetches the count with the tokens):
``gated_delta_step_roofline``'s pass over the trace under another
span's name, priced by the family's ``ssd_step_bytes``. Parameters:
``patterns``, ``span``. A program with no such span (a checkout from
before the family), or a trace with no such kernel, gives nothing to
read."""
from .. import manifest
from . import kernel_launches
from .gated_delta_step_roofline import advanced_slots


def read(run, params):
    found = advanced_slots(run, params["span"])
    if not found:
        return None
    launches, seconds = kernel_launches.held(run, params, len(found),
                                             "steps")
    if not launches or seconds <= 0:
        return None
    family = manifest.plugin("models", run.config["family"])
    nbytes = family.ssd_step_bytes(
        run.config["model"], sum(found) * launches / len(found))
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
