"""The paged-attention decode kernel's share of its roofline: the
least time the chip's memory could take to deliver the keys and values
of the pages that held live tokens in the window's decode steps (bytes
from shapes over the HBM peak; decode attention is bandwidth-bound)
over the device time of the kernel's events. Parameters: ``patterns``."""
from .. import manifest


def read(run, params):
    count, seconds = run.reduction.matching(params["patterns"])
    pages = run.counters.get("live_kv_pages_read")
    if not count or not pages:
        return None
    family = manifest.plugin("models", run.config["family"])
    nbytes = family.paged_attention_bytes(
        run.config["model"], run.config["inference"]["kv_block_size"],
        pages)
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
