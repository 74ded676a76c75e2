"""Worker for the 2-process jax.distributed CPU smoke test.

Launched twice by test_two_process.py with RANK/WORLD_SIZE/MASTER_ADDR env
(the same launcher surface deepspeed_tpu.init_distributed consumes). Each
process owns 2 virtual CPU devices -> a 4-way data mesh across 2 processes.

Covers the full multi-process engine surface the single-process suite
cannot: distributed init, per-process batch sharding
(make_array_from_process_local_data), multi-process ZeRO-Offload (host
shards per process: reference stage2.py:780-908), and checkpoint
save/load with per-process zero shard files.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=2")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")   # the virtual CPU mesh, always
import jax.numpy as jnp


def main():
    rank = int(os.environ["RANK"])
    ckpt_dir = sys.argv[1]

    import deepspeed_tpu
    from deepspeed_tpu.runtime.model import Model

    deepspeed_tpu.init_distributed()
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 4, jax.device_count()

    def apply_fn(params, x, y):
        return jnp.mean((x @ params["w"] - y) ** 2)

    config = {
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 5e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2, "cpu_offload": True},
        "steps_per_print": 1000,
    }

    def make_engine():
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=Model(apply_fn, {"w": jnp.zeros((32, 8))}),
            config_params=config)
        return engine

    engine = make_engine()
    assert engine.dp_world_size == 4

    # multi-process offload: host shards must cover only OUR grads
    n_shard_elems = sum(int(p.size)
                        for shards in engine.host_state["shard_leaves"]
                        for _, p, _, _ in shards)
    assert n_shard_elems == 32 * 8 // 2, \
        "each process must hold half the master: {}".format(n_shard_elems)

    rs = np.random.RandomState(0)          # SAME data on both ranks...
    W = rs.randn(32, 8).astype(np.float32)
    losses = []
    for step in range(30):
        xg = np.random.RandomState(100 + step).randn(16, 32) \
            .astype(np.float32)
        yg = xg @ W
        # ...but each process feeds only its LOCAL half of the batch
        lo, hi = rank * 8, (rank + 1) * 8
        loss = engine(xg[lo:hi], yg[lo:hi])
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < 0.2 * losses[0], losses

    engine.save_checkpoint(ckpt_dir)

    engine2 = make_engine()
    path, _ = engine2.load_checkpoint(ckpt_dir)
    assert path is not None
    assert engine2.host_state["step"] == 30
    # same shard layout restored bit-exact
    for sh_a, sh_b in zip(engine.host_state["shard_leaves"],
                          engine2.host_state["shard_leaves"]):
        for (ia, pa, ma, va), (ib, pb, mb, vb) in zip(sh_a, sh_b):
            assert ia == ib
            np.testing.assert_array_equal(pa, pb)
            np.testing.assert_array_equal(ma, mb)
            np.testing.assert_array_equal(va, vb)

    xg = np.random.RandomState(999).randn(16, 32).astype(np.float32)
    yg = xg @ W
    lo, hi = rank * 8, (rank + 1) * 8
    l1 = float(engine(xg[lo:hi], yg[lo:hi]))
    l2 = float(engine2(xg[lo:hi], yg[lo:hi]))
    assert abs(l1 - l2) < 1e-6, (l1, l2)

    # --- device-state ZeRO: per-rank zero shard files (no offload) ---
    # Each process writes zero_pp_rank_<rank>; the model file carries no
    # optimizer/master (reference engine.py:1350-1377 layout), and resume
    # reassembles bit-exact state from the shard set.
    dev_dir = os.path.join(ckpt_dir, "device_zero")
    dev_config = dict(config)
    dev_config["zero_optimization"] = {"stage": 2}

    def make_dev_engine():
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=Model(apply_fn, {"w": jnp.zeros((32, 8))}),
            config_params=dev_config)
        return eng

    dev = make_dev_engine()
    for step in range(10):
        xg = np.random.RandomState(200 + step).randn(16, 32) \
            .astype(np.float32)
        yg = xg @ W
        loss = dev(xg[lo:hi], yg[lo:hi])
        dev.backward(loss)
        dev.step()
    dev.save_checkpoint(dev_dir, tag="tag0")

    from deepspeed_tpu.runtime import checkpointing as ckpt_mod
    my_zero = ckpt_mod.zero_ckpt_name(dev_dir, "tag0", dp_rank=rank)
    assert os.path.isfile(my_zero), my_zero
    sd = ckpt_mod.load_state_dict(
        ckpt_mod.model_ckpt_name(dev_dir, "tag0"))
    assert sd["optimizer"] is None and sd["master"] is None, \
        "model file must not duplicate the sharded optimizer state"

    dev2 = make_dev_engine()
    path, _ = dev2.load_checkpoint(dev_dir, tag="tag0")
    assert path is not None

    def assert_shards_equal(ta, tb):
        # leaves span processes; compare this process's shards
        for a, b in zip(jax.tree_util.tree_leaves(ta),
                        jax.tree_util.tree_leaves(tb)):
            for sa, sb in zip(a.addressable_shards, b.addressable_shards):
                assert sa.index == sb.index
                np.testing.assert_array_equal(np.asarray(sa.data),
                                              np.asarray(sb.data))

    assert_shards_equal(dev.state["master"], dev2.state["master"])
    for key in ("exp_avg", "exp_avg_sq"):
        assert_shards_equal(dev.state["opt"][key], dev2.state["opt"][key])
    xg = np.random.RandomState(998).randn(16, 32).astype(np.float32)
    yg = xg @ W
    d1 = float(dev(xg[lo:hi], yg[lo:hi]))
    d2 = float(dev2(xg[lo:hi], yg[lo:hi]))
    assert abs(d1 - d2) < 1e-6, (d1, d2)

    print("DIST_OK rank={} final_loss={:.6f} resume_loss={:.6f}".format(
        rank, losses[-1], l2), flush=True)


if __name__ == "__main__":
    main()
