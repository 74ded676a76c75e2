"""The prefill selective-scan kernel's share of its HBM roofline: the
least time the chip's memory could take to move the scan's operands
and results for the padded tokens of the prefill chunks that the trace
holds whole (bytes from shapes over the HBM peak; a chunk's padded
tokens are the mean over the window's chunk spans) over the device
time of the kernel's events in those chunks (``kernel_launches``).
Parameters: ``patterns``, ``span`` (the chunk span)."""
from .. import manifest
from . import kernel_launches, span_chunks


def read(run, params):
    found = span_chunks.chunks(run, params["span"])
    if not found:
        return None
    launches, seconds = kernel_launches.held(run, params, len(found),
                                             "chunks")
    if not launches:
        return None
    family = manifest.plugin("models", run.config["family"])
    padded = sum(p for _, p in found) * launches / len(found)
    nbytes = family.mamba_scan_bytes(run.config["model"], padded, launches)
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
