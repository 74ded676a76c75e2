"""Model FLOP/s utilisation of a serving run: the tokens the window's
steps prefilled and generated per second and chip, times the operations
a token needs in a forward pass (the model family's count), over the
chip's published bf16 peak. Parameters: ``rate`` (the run's rate to
price, which every serving run measures, also where a tail is the
cell's end-to-end metric)."""
from .. import manifest


def read(run, params):
    rate = run.end_to_end.get(params["rate"])
    if not rate:
        return None
    family = manifest.plugin("models", run.config["family"])
    flops = family.serve_flops_per_token(run.config["model"])
    return 100.0 * rate * flops / run.peaks["bf16_flops_per_s"]
