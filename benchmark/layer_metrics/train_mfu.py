"""Model FLOP/s utilisation of a training run: tokens per second and
chip, times the operations forward and backward need per token (the
model family's count: no recomputation), over the chip's published
bf16 peak. Parameters: ``rate`` (the end-to-end metric to price)."""
from .. import manifest


def read(run, params):
    rate = run.end_to_end.get(params["rate"])
    seq = run.counters.get("seq_len")
    if rate is None or seq is None:
        return None
    family = manifest.plugin("models", run.config["family"])
    flops = family.train_flops_per_token(run.config["model"], seq)
    return 100.0 * rate * flops / run.peaks["bf16_flops_per_s"]
