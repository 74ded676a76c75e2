"""The prefill selective-scan kernel's share of its HBM roofline: the
least time the chip's memory could take to move the scan's operands
and results for the window's padded chunk tokens (bytes from shapes
over the HBM peak) over the device time of the kernel's events.
Parameters: ``patterns``, ``span`` (the chunk span)."""
from .. import manifest
from . import span_chunks


def read(run, params):
    count, seconds = run.reduction.matching(params["patterns"])
    found = span_chunks.chunks(run, params["span"])
    if not count or not found:
        return None
    family = manifest.plugin("models", run.config["family"])
    nbytes = family.mamba_scan_bytes(
        run.config["model"], sum(p for _, p in found), len(found))
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
