"""Device time of ``ops/moe.py::expert_ffn`` for one layer where the chip
holds a SHARE of the experts, beside the same real rows with nothing
routed elsewhere: what an eighth of real rows costs. ONE chip, bfloat16,
read from a profiler trace (not a host clock). Needs a TPU.

    chiprun -- python tests/perf/expert_share_microbench.py \
        [--tokens 2048,40] [--width 4096] [--experts 128] [--held 16] \
        [--top-k 8] [--skew 0.25] [--root DIR]

For each token count two lines. ``share``: ``tokens x top_k`` rows are
routed and sorted, of which the ``held / experts`` that land here are
real; since PR 52 only those are gathered, multiplied, activated and
combined, ``share_capacity`` rows at a time (command-a-plus-serve.rag: a
chunk's 16,384 rows, 2,048 real, a capacity of 4,096: ``moe_gmm``'s grid
carries 32 + 15 item slots, where until PR 52 it carried one for every
row tile of the 16,384). ``all_real``: the same number of REAL rows a
held expert (``tokens x top_k x held / experts``, evenly) with every row
routed to an expert held here, which is the layer an exchange in front
of it would hand this chip. ``--skew S`` forces that share of the tokens
wholly onto held experts in the ``share`` line, so that a launch past
its capacity (two passes at 0.25 where 16 of 128 are held) has a device
time too; the line's ``passes`` says how many it took. ``--root`` runs
another checkout's ``deepspeed_tpu`` (the parent commit unpacked under
``_chip_checkout/``). Each line: the whole call's device ms, the
``moe_gmm`` kernel's (both matmuls, every pass), the rest (sort, gather,
activation, combine) and the least time the held experts' matrices take
to stream from HBM.
"""
import argparse
import json
import os
import sys
import tempfile

from flash_attention_microbench import kernel_ms

ITERS = 5
PEAK_BYTES_PER_S = 819e9      # benchmark/peaks.json, TPU v5e


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="2048,40")
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    ns = ap.parse_args()
    sys.path.insert(0, os.path.abspath(ns.root))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops import moe
    if jax.devices()[0].platform != "tpu":
        sys.exit("the micro-benchmark measures device time: it needs a TPU")
    d = ff = ns.width
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    w13 = (0.02 * jax.random.normal(keys[0], (ns.held, d, 2 * ff),
                                    jnp.float32)).astype(jnp.bfloat16)
    w2 = (0.02 * jax.random.normal(keys[1], (ns.held, ff, d),
                                   jnp.float32)).astype(jnp.bfloat16)
    stream_ms = 1e3 * (w13.nbytes + w2.nbytes) / PEAK_BYTES_PER_S
    rng = np.random.default_rng(0)
    for tokens in map(int, ns.tokens.split(",")):
        x = jax.random.normal(keys[2], (tokens, d), jnp.bfloat16)
        # share: top_k distinct experts of all, a token
        chosen = np.stack([rng.permutation(ns.experts)[:ns.top_k]
                           for _ in range(tokens)]).astype(np.int32)
        # skew: the first tokens' choices all among the held
        for t in range(int(round(ns.skew * tokens))):
            chosen[t] = rng.permutation(ns.held)[:ns.top_k]
        real = int((chosen < ns.held).sum())
        # all_real: as many rows, each token's choices among the held
        k_real = max(1, round(ns.top_k * ns.held / ns.experts))
        some = np.stack([rng.permutation(ns.held)[:k_real]
                         for _ in range(tokens)]).astype(np.int32)
        for label, picks, experts in (("share", chosen, ns.experts),
                                      ("all_real", some, ns.held)):
            weights = jnp.full(picks.shape, 1.0 / picks.shape[1],
                               jnp.float32)
            fn = jax.jit(lambda x, c, w, experts=experts: moe.expert_ffn(
                x, c, w, w13, w2, (0, ns.held), experts, kernel="pallas"))
            args = (x, jnp.asarray(picks), weights)
            jax.block_until_ready(fn(*args))
            load = jax.block_until_ready(fn(*args))[1]
            passes = int(moe.share_passes(load, picks.size, (0, ns.held),
                                          experts)) \
                if hasattr(moe, "share_passes") else 1
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                for _ in range(ITERS):
                    out = fn(*args)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                kernels, busy_ms, others = kernel_ms(tmp, ITERS)
            gmm = kernels.get("moe_gmm", 0.0)
            print(json.dumps(dict(
                case=label, tokens=tokens, rows=int(picks.size),
                real_rows=real if label == "share" else int(picks.size),
                passes=passes,
                call_ms=busy_ms, moe_gmm_ms=gmm,
                rest_ms=round(busy_ms - gmm, 4),
                weights_stream_ms=round(stream_ms, 4), largest_others=others,
                device=jax.devices()[0].device_kind)), flush=True)


if __name__ == "__main__":
    main()
