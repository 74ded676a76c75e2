"""The flash-attention kernels' share of their roofline in a training
run: the least time the chip could take for the causal attention of
the training steps that the trace holds whole (operations from shapes
over the bf16 peak; the kernel is compute-bound at these shapes) over
the device time of the kernels' events in those steps
(``kernel_launches``). Parameters: ``patterns`` (substrings that mark
the kernels' events in the trace)."""
from .. import manifest
from . import kernel_launches


def read(run, params):
    steps = run.counters.get("steps")
    if not steps:
        return None
    launches, seconds = kernel_launches.held(run, params, steps, "steps")
    if not launches:
        return None
    family = manifest.plugin("models", run.config["family"])
    flops = launches * family.flash_attention_flops(
        run.config["model"], run.counters["rows"],
        run.counters["seq_len"])
    return 100.0 * (flops / run.peaks["bf16_flops_per_s"]) / seconds
