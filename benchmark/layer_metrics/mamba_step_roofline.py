"""The decode state-update kernel's share of its HBM roofline: the
least time the chip's memory could take to read and write the live
slots' recurrent state in the decode steps that the trace holds whole
(bytes from shapes over the HBM peak; a step's live slots are the
window's mean, ``active_slot_steps`` over ``steps``) over the device
time of the kernel's events in those steps (``kernel_launches``).
Parameters: ``patterns``."""
from .. import manifest
from . import kernel_launches


def read(run, params):
    slot_steps = run.counters.get("active_slot_steps")
    steps = run.counters.get("steps")
    if not slot_steps or not steps:
        return None
    launches, seconds = kernel_launches.held(run, params, steps, "steps")
    if not launches:
        return None
    family = manifest.plugin("models", run.config["family"])
    nbytes = family.mamba_step_bytes(
        run.config["model"], slot_steps * launches / steps,
        run.config["precision_state"])
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
