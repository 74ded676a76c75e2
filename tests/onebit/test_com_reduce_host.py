"""Standalone correctness check: compressed allreduce vs exact allreduce.

Reference-parity tier-4 script (reference tests/onebit/test_nccl_backend.py
— a manually-launched validation of NcclBackend.compressed_allreduce
against torch.distributed.all_reduce). Here the backend is XLA collectives
on a virtual device mesh, so it runs anywhere:

    python tests/onebit/test_com_reduce_host.py [--devices 8] [--size 16384]

Validates:
  * one compressed round has bounded error vs the exact mean;
  * with error feedback carried across rounds on a CONSTANT input, the
    accumulated compressed estimate converges toward the exact mean
    (the property 1-bit Adam's convergence rests on).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--size", type=int, default=16384)
    parser.add_argument("--rounds", type=int, default=120)
    args = parser.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"   # virtual mesh
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count={}".format(args.devices))

    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.runtime.comm.compressed import CompressedBackend

    world, n = args.devices, args.size
    mesh = build_mesh(data=world)
    backend = CompressedBackend(mesh)

    rng = np.random.RandomState(7)
    values = jnp.asarray(rng.randn(world, n).astype(np.float32))
    exact = np.asarray(values.mean(axis=0))

    # one round: bounded relative error
    out, we, se = backend.compressed_allreduce(values)
    out0 = np.asarray(out[0])
    rel = np.linalg.norm(out0 - exact) / np.linalg.norm(exact)
    print("one-round relative error: {:.3f}".format(rel))
    assert rel < 1.0, "sign-compression error out of bounds"
    assert np.all(np.asarray(out) == out0), "ranks disagree"

    # error feedback: sum of compressed outputs tracks t * exact mean
    we = se = None
    acc = np.zeros_like(exact)
    for t in range(1, args.rounds + 1):
        out, we, se = backend.compressed_allreduce(values, we, se)
        acc += np.asarray(out[0])
        drift = np.linalg.norm(acc / t - exact) / np.linalg.norm(exact)
    print("after {} rounds with error feedback: drift {:.4f}".format(
        args.rounds, drift))
    assert drift < 0.05, "error feedback failed to converge"
    print("PASSED")


if __name__ == "__main__":
    main()
