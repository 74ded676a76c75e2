"""Paged-attention microbench: Pallas page-walk kernel vs XLA gather.

Two paged serving engines of the same small GPT-2 — the XLA
``jnp.take`` gather-back oracle vs the ``ops/pallas/paged_attention``
in-kernel page walk (``inference.paged_attention_kernel``) — driving
the SAME greedy decode workload in INTERLEAVED blocks (sequential
whole-run blocks alias machine drift on a shared box; the
bench_telemetry_overhead.py discipline). Emits one JSON line in
bench.py's shape (validated by bin/check_bench_schema.py) plus the
committed artifact tests/perf/BENCH_PAGED_ATTN.json.

value = kernel-path median decode-step time; vs_baseline = gather /
kernel (> 1 means the kernel is faster). On the CPU rung the kernel
runs under the Pallas INTERPRETER (per-op python dispatch), so the
honest expectation is vs_baseline << 1 — the artifact pins the
harness, the byte-identical greedy streams, and the decode-program
shape; the bytes-touched win (2 pages vs the full logical window per
slot per layer) is a TPU claim (docs/pallas_kernels.md).
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROUNDS = 4
BLOCK = 6          # decode steps per block
WARMUP = 2
NUM_SLOTS = 4
PAGE_SIZE = 8


def _engine(kernel):
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(vocab_size=512, max_seq_len=256, n_layers=2,
                          n_heads=4, d_model=128,
                          use_flash_attention=False, remat=False,
                          loss_chunk=0)
    eng = deepspeed.init_inference(
        model=gpt2.make_gpt2_model(config=cfg),
        config={"inference": {
            "max_batch_size": NUM_SLOTS, "prefill_buckets": [64],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": PAGE_SIZE,
            "paged_attention_kernel": kernel}})
    assert eng.paged_attention_kernel == kernel
    return eng


def main():
    import jax
    eng_x = _engine("xla")
    eng_p = _engine("pallas")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 512, size=40 + 7 * i).tolist()
               for i in range(NUM_SLOTS)]

    # occupy every slot with a prefilled sequence, then drive the fused
    # all-slot decode step directly — the program under test
    pend = {}
    for name, eng in (("xla", eng_x), ("pallas", eng_p)):
        toks = []
        for slot, prompt in enumerate(prompts):
            assert eng.try_admit(slot, prompt)
            toks.append(eng.prefill(slot, prompt))
        pend[name] = np.asarray(toks, np.int32)

    def decode(eng, name):
        for slot in range(NUM_SLOTS):
            assert eng.ensure_pages(slot, int(eng.lengths[slot]) + 1)
        chosen = eng.decode_step(pend[name])
        for slot in range(NUM_SLOTS):
            eng.advance(slot)
        pend[name] = np.asarray(chosen, np.int32)
        return chosen

    streams = {"xla": [], "pallas": []}
    for name, eng in (("xla", eng_x), ("pallas", eng_p)):
        for _ in range(WARMUP):
            streams[name].append(decode(eng, name).tolist())
    times = {"xla": [], "pallas": []}
    ratios = []
    for r in range(ROUNDS):
        order = [("xla", eng_x), ("pallas", eng_p)]
        if r % 2:
            order.reverse()
        med = {}
        for name, eng in order:
            block = []
            for _ in range(BLOCK):
                t0 = time.time()
                chosen = decode(eng, name)
                block.append(time.time() - t0)
                streams[name].append(chosen.tolist())
            times[name].extend(block)
            med[name] = float(np.median(block))
        ratios.append(med["xla"] / med["pallas"])

    # the acceptance bit, measured on the bench workload itself: every
    # decode step's chosen tokens byte-identical across read paths
    assert streams["xla"] == streams["pallas"], "streams diverged"

    xla = float(np.median(times["xla"]))
    pal = float(np.median(times["pallas"]))
    payload = {
        "metric": "paged_attention_pallas_decode_step_time",
        "value": round(pal, 6),
        "unit": "s/step",
        # gather/kernel median-of-paired-ratios: > 1 means kernel faster
        "vs_baseline": round(float(np.median(ratios)), 4),
        "extra": {
            "median_step_s_xla_gather": round(xla, 6),
            "median_step_s_pallas": round(pal, 6),
            "per_round_xla_pallas_ratios": [round(r, 4) for r in ratios],
            "decode_steps_per_engine": WARMUP + ROUNDS * BLOCK,
            "greedy_streams_byte_identical": True,
            "num_slots": NUM_SLOTS,
            "page_size": PAGE_SIZE,
            "seq_lens_at_start": [len(p) for p in prompts],
            "interpreter_mode": jax.default_backend() != "tpu",
            "device": getattr(jax.devices()[0], "device_kind", "cpu"),
            "backend": jax.default_backend(),
        },
    }
    print(json.dumps(payload))
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_PAGED_ATTN.json")
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
