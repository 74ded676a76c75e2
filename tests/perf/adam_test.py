"""CPU Adam micro-benchmark (reference tests/perf/adam_test*.py).

Standalone: python tests/perf/adam_test.py [numel]
Times the native SIMD C++ op against the XLA-CPU fallback.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(numel=8 * 1024 * 1024, iters=10):
    import jax
    # host benchmark: force the CPU backend
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from deepspeed_tpu.ops.adam.fused_adam import adam_init, adam_update

    rs = np.random.RandomState(0)
    params = {"flat": jnp.asarray(rs.randn(numel), dtype=jnp.float32)}
    grads = {"flat": jnp.asarray(rs.randn(numel), dtype=jnp.float32)}

    # use_native=True forces the native leg (fails loudly if unbuilt) so
    # the comparison stays meaningful on hosts where the auto gate would
    # pick XLA.
    for use_native, label in ((False, "xla-cpu"), (True, "native")):
        from deepspeed_tpu.ops.adam.fused_adam import DeepSpeedCPUAdam
        opt = DeepSpeedCPUAdam(lr=1e-3, use_native=use_native)
        state = opt.init_state(params)
        h = opt.hyperparams()
        opt.update(grads, state, params, **h)  # warmup/compile/build
        t0 = time.time()
        for _ in range(iters):
            _, state = opt.update(grads, state, params, **h)
        dt = (time.time() - t0) / iters
        print("{}: {:.2f} ms / step for {:,} params ({:.1f} GB/s)".format(
            label, dt * 1e3, numel, numel * 16 / dt / 1e9))


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:]))
