"""The decode state-update kernel's share of its HBM roofline: the
least time the chip's memory could take to read and write the live
slots' recurrent state in the window's decode steps (bytes from shapes
over the HBM peak) over the device time of the kernel's events.
Parameters: ``patterns``."""
from .. import manifest


def read(run, params):
    count, seconds = run.reduction.matching(params["patterns"])
    slot_steps = run.counters.get("active_slot_steps")
    if not count or not slot_steps:
        return None
    family = manifest.plugin("models", run.config["family"])
    nbytes = family.mamba_step_bytes(
        run.config["model"], slot_steps, run.config["precision_state"])
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
