"""A prompt chunk's attention against the chip's bf16 peak: the least
time the MXU could take for the operations that the queries of the
prefill launches the trace holds whole (``kernel_launches``) MUST spend,
over the device time of the kernel's events in those launches. What a
chunk must spend follows from where it begins and how many tokens it
holds (``start`` and ``tokens`` of the program's own
``sched.prefill.chunk`` spans) and from the layer kinds: the query at
``t`` visits ``t + 1`` keys in a full layer and ``min(t + 1, window)`` in
a sliding one, counted by the model family's ``chunk_attention_flops``,
so the share prices the work and not the kernel's tiles (padding to the
bucket, a block-diagonal query's idle lanes and blocks past the causal
edge are in the time only). The work of one launch is the window's mean
over its chunks. Parameters: ``patterns``, ``span``. A program whose
spans carry no ``start``, or a trace with no such kernel, gives nothing
to read."""
from .. import manifest
from . import kernel_launches, program_spans


def read(run, params):
    window = run.reduction
    chunks = [(int(ev[3]["start"]), int(ev[3]["tokens"]))
              for ev in program_spans.load(run).named([params["span"]])
              if "start" in ev[3] and "tokens" in ev[3] and
              (window.window_s <= 0 or window.start <= ev[2] <= window.end)]
    if not chunks:
        return None
    launches, seconds = kernel_launches.held(run, params, len(chunks),
                                             "chunks")
    if not launches or seconds <= 0:
        return None
    family = manifest.plugin("models", run.config["family"])
    flops = sum(family.chunk_attention_flops(run.config["model"], start, n)
                for start, n in chunks) * launches / len(chunks)
    return 100.0 * (flops / run.peaks["bf16_flops_per_s"]) / seconds
