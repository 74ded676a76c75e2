"""The paged-attention decode kernel's share of its roofline: the
least time the chip's memory could take to deliver the keys and values
of the pages that held live tokens in the decode steps that the trace
holds whole (bytes from shapes over the HBM peak; decode attention is
bandwidth-bound; a step's live pages are the window's mean,
``live_kv_pages_read`` over ``steps``) over the device time of the
kernel's events in those steps (``kernel_launches``). Parameters:
``patterns``."""
from .. import manifest
from . import kernel_launches


def read(run, params):
    pages = run.counters.get("live_kv_pages_read")
    steps = run.counters.get("steps")
    if not pages or not steps:
        return None
    launches, seconds = kernel_launches.held(run, params, steps, "steps")
    if not launches:
        return None
    family = manifest.plugin("models", run.config["family"])
    nbytes = family.paged_attention_bytes(
        run.config["model"], run.config["inference"]["kv_block_size"],
        pages * launches / steps)
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
