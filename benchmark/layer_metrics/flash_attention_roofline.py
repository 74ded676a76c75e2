"""The flash-attention kernels' share of their roofline in a training
run: the least time the chip could take for the causal attention of
the window's steps (operations from shapes over the bf16 peak; the
kernel is compute-bound at these shapes) over the device time of the
kernels' events. Parameters: ``patterns`` (substrings that mark the
kernels' events in the trace)."""
from .. import manifest


def read(run, params):
    count, seconds = run.reduction.matching(params["patterns"])
    steps = run.counters.get("steps")
    if not count or not steps:
        return None
    family = manifest.plugin("models", run.config["family"])
    flops = steps * family.flash_attention_flops(
        run.config["model"], run.counters["rows"],
        run.counters["seq_len"])
    return 100.0 * (flops / run.peaks["bf16_flops_per_s"]) / seconds
