"""Standalone micro-benchmark: compressed vs exact allreduce wall time.

Reference-parity tier-4 script (reference tests/onebit/test_nccl_perf.py /
test_mpi_perf.py — manually-launched timing of the compressed allreduce).
On a CPU mesh the numbers only show the mechanism; on a pod the compressed
path wins whenever the wire (DCN) is the bottleneck — the reference's
"6.6x compression-stage speedup at 40 Gb Ethernet" regime.

    python tests/onebit/test_com_perf.py [--devices 8] [--size 4194304]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def timeit(fn, *args, reps=10):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--size", type=int, default=1 << 22)
    args = parser.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"   # virtual mesh
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count={}".format(args.devices))

    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from deepspeed_tpu.parallel.topology import build_mesh, DATA_AXIS
    from deepspeed_tpu.runtime.comm.compressed import CompressedBackend

    world, n = args.devices, args.size
    mesh = build_mesh(data=world)
    backend = CompressedBackend(mesh)

    rng = np.random.RandomState(0)
    values = jnp.asarray(rng.randn(world, n).astype(np.float32))

    @jax.jit
    def exact(v):
        f = shard_map(lambda x: jax.lax.pmean(x, DATA_AXIS),
                      mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS))
        return f(v)

    # Error buffers live at the backend's padded width, not n — sizes not
    # divisible by 8*devices would shape-error inside jit otherwise.
    we = jnp.zeros((world, backend.padded_size(n)), jnp.float32)
    se = jnp.zeros((world, backend.padded_size(n) // world), jnp.float32)

    t_exact = timeit(exact, values)
    t_comp = timeit(lambda v: backend.compressed_allreduce(v, we, se), values)
    mb = n * 4 / 1e6
    print("buffer {:.1f} MB x {} ranks".format(mb, world))
    print("exact allreduce:      {:.2f} ms".format(t_exact * 1e3))
    print("compressed allreduce: {:.2f} ms (wire 32x smaller)".format(
        t_comp * 1e3))


if __name__ == "__main__":
    main()
