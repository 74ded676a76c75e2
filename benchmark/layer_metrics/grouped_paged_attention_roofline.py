"""The grouped page walk's share of its roofline where a model's paged
layers stand in GROUPS, one of them behind a sliding window: the least
time the chip's memory could take to deliver the keys and values of
both groups' live pages in the decode steps that the trace holds whole,
over the device time of the kernel's events in those steps
(``kernel_launches``). A step's live pages are the window's mean of what
the program's own ``sched.decode.pages`` spans say the decoding slots
held (``full_live`` pages of the groups without a window, ``window_live``
of the windowed: only pages with a key some query sees), priced a group
at its own bytes a page by the family's ``paged_attention_bytes``.
Parameters: ``patterns``, ``span``. A program whose spans carry no such
attributes, or a trace with no such kernel, gives nothing to read."""
from .. import manifest
from . import kernel_launches, program_spans


def live_pages(run, span):
    """[(full_live, window_live)] of the ``span`` events that ended in
    the traced window."""
    window = run.reduction
    return [(int(ev[3]["full_live"]), int(ev[3]["window_live"]))
            for ev in program_spans.load(run).named([span])
            if "full_live" in ev[3] and "window_live" in ev[3] and
            (window.window_s <= 0 or window.start <= ev[2] <= window.end)]


def read(run, params):
    found = live_pages(run, params["span"])
    if not found:
        return None
    launches, seconds = kernel_launches.held(run, params, len(found),
                                             "steps")
    if not launches or seconds <= 0:
        return None
    family = manifest.plugin("models", run.config["family"])
    scale = launches / len(found)
    nbytes = family.paged_attention_bytes(
        run.config["model"], run.config["inference"]["kv_block_size"],
        sum(f for f, _ in found) * scale, sum(w for _, w in found) * scale)
    return 100.0 * (nbytes / run.peaks["hbm_bytes_per_s"]) / seconds
