"""The chip-facing entry points, as far as a CPU can hold them to their
contract: no script passes without a TPU, no import touches a backend,
the peak tables refuse unknown devices, the compile cache is placed from
outside, and the flash op survives a data-parallel mesh."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120, **kw)


# ------------------------------------------------------- no chip, no result
def test_entry_script_fails_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero at its device
    check with a one-line reason — no stand-in model, no result line
    (the benchmark's own refusal: tests/unit_benchmark/)."""
    res = _run(["chip_smoke.py"])
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert '"value"' not in res.stdout
    assert "needs a TPU" in res.stderr
    assert "platform 'cpu'" in res.stderr


def test_chip_smoke_four_chips_fails_without_a_tpu():
    res = _run(["chip_smoke.py", "--four-chips"])
    assert res.returncode != 0 and '"ok": true' not in res.stdout


def test_imports_initialise_no_backend():
    """A parent that touched a backend holds the chip: importing the
    package, the launcher, the model and the serving subsystem must not."""
    code = (
        "import deepspeed_tpu, deepspeed_tpu.launcher.runner, "
        "deepspeed_tpu.launcher.launch, deepspeed_tpu.models.gpt2, "
        "deepspeed_tpu.inference, deepspeed_tpu.utils.compile_cache\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "list(xla_bridge._backends)\n")
    res = _run(["-c", code])
    assert res.returncode == 0, res.stderr[-2000:]


# ------------------------------------------------------------- peak tables
def test_peak_tables_match_device_kind_exactly():
    from deepspeed_tpu.runtime.comm.wire import ici_bytes_per_s_for
    from deepspeed_tpu.telemetry.mfu import peak_flops_for
    assert peak_flops_for("TPU v5 lite") == 197e12
    assert peak_flops_for("TPU v5") == 459e12     # no prefix matching
    assert ici_bytes_per_s_for("TPU v5 lite") == 400e9
    assert peak_flops_for(jax.devices()[0]) == 0.1e12   # tier-1 nominal
    for unknown in ("TPU v5 litex", "tpu v5 lite", "TPU v9", ""):
        with pytest.raises(KeyError, match="device kind"):
            peak_flops_for(unknown)
        with pytest.raises(KeyError, match="device kind"):
            ici_bytes_per_s_for(unknown)


# ------------------------------------------------------------ compile cache
def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    from deepspeed_tpu.utils import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "given"))
    assert compile_cache.compile_cache_dir() == str(tmp_path / "given")
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.compile_cache_dir() == \
        os.path.join(REPO, ".jax_cache")
    # fixed: no temp dir, pid or time in the path
    assert compile_cache.compile_cache_dir() == \
        compile_cache.compile_cache_dir()


def test_enable_compile_cache_sets_no_directory_when_env_given():
    code = (
        "import os, jax\n"
        "from deepspeed_tpu.utils.compile_cache import "
        "enable_compile_cache\n"
        "given = os.environ.get('JAX_COMPILATION_CACHE_DIR')\n"
        "knobs = ('jax_persistent_cache_min_compile_time_secs', "
        "'jax_persistent_cache_min_entry_size_bytes')\n"
        "read = lambda: [getattr(jax.config, k) for k in knobs]\n"
        "before, knobs_before = jax.config.jax_compilation_cache_dir, "
        "read()\n"
        "path = enable_compile_cache()\n"
        "after = jax.config.jax_compilation_cache_dir\n"
        "assert jax.config.jax_enable_compilation_cache\n"
        "assert read() == knobs_before   # enables, tunes nothing\n"
        "if given:\n"
        "    assert path == given and after == before == given, after\n"
        "else:\n"
        "    assert path == after and path.endswith('.jax_cache'), after\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    for env in ({"JAX_COMPILATION_CACHE_DIR": "/nonexistent/given"}, {}):
        full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
        if not env:
            full.pop("JAX_COMPILATION_CACHE_DIR", None)
        res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                             env=full, capture_output=True, text=True,
                             timeout=120)
        assert res.returncode == 0, res.stderr[-2000:]


# ------------------------------------------- fused optimizer kernel choice
def test_forced_pallas_optimizer_with_bf16_moments_raises():
    from deepspeed_tpu.ops.pallas_utils import resolve_fused_kernel
    assert resolve_fused_kernel(None, jnp.bfloat16) == "xla"
    assert resolve_fused_kernel(False, jnp.float32) == "xla"
    assert resolve_fused_kernel(True, jnp.float32) == "interpret"  # CPU
    with pytest.raises(ValueError, match="fp32-state"):
        resolve_fused_kernel(True, jnp.bfloat16)


# ------------------------------------------- Mosaic kernels on a mesh
def _tiny_block(d=32, seed=0):
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(vocab_size=64, max_seq_len=32, n_layers=1,
                          n_heads=2, d_model=d, remat=False,
                          flash_attention_backend="interpret")
    block = gpt2.init_block_params(cfg, np.random.RandomState(seed))
    block["ln1"]["scale"] = block["ln1"]["scale"] * 1.5
    return cfg, block


def _assert_trees_close(got, want, tol):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("axes", [dict(data=8), dict(data=4, model=2)],
                         ids=["data8", "data4_model2"])
def test_fused_flash_op_runs_under_a_shard_map_over_the_mesh(axes):
    """GSPMD cannot partition a Mosaic kernel: handed the mesh, the fused
    flash op runs under a shard_map over every axis of it — same values
    and gradients as the bare op, batch rows split over ``data``."""
    from deepspeed_tpu.ops.transformer.attention import \
        fused_causal_attention
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(**axes)
    cfg, block = _tiny_block()
    x = jnp.asarray(np.random.RandomState(1).randn(8, 32, 32), jnp.float32)
    weights = (block["ln1"]["scale"], block["ln1"]["bias"],
               block["attn"]["qkv_kernel"], block["attn"]["qkv_bias"])

    def loss(mesh):
        def f(x, weights):
            return (fused_causal_attention(
                x, *weights, cfg.n_heads, interpret=True,
                mesh=mesh) ** 2).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

    jaxpr = lambda m, x: str(jax.make_jaxpr(lambda x: fused_causal_attention(
        x, *weights, cfg.n_heads, interpret=True, mesh=m))(x))
    assert "shard_map" in jaxpr(mesh, x)
    assert "shard_map" not in jaxpr(None, x)
    # rows the data axis does not divide enter replicated, still manual
    assert "shard_map" in jaxpr(mesh, x[:3])

    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    v0, g0 = loss(None)(x, weights)
    v1, g1 = loss(mesh)(xs, weights)
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
    _assert_trees_close(g1, g0, 1e-4)


def test_flash_attention_splits_heads_over_the_model_axis():
    """The unfused dispatch (the path a tensor-parallel mesh takes):
    batch over ``data``, heads over ``model``."""
    from deepspeed_tpu.ops.transformer.attention import causal_attention
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(data=4, model=2)
    rs = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rs.randn(4, 32, 2, 16), jnp.float32)
               for _ in range(3))

    def grads(backend, mesh):
        def f(q, k, v):
            return (causal_attention(q, k, v, backend=backend,
                                     mesh=mesh) ** 2).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)

    assert "shard_map" in str(jax.make_jaxpr(
        lambda q: causal_attention(q, k, v, backend="interpret",
                                   mesh=mesh))(q))
    _assert_trees_close(grads("interpret", mesh), grads("xla", None), 1e-4)


@pytest.mark.parametrize("outer", [("data",), ("data", "model")],
                         ids=["partly_manual", "fully_manual"])
def test_shard_kernel_inside_an_engines_own_shard_map(outer):
    """Where an engine's shard_map already bound some axes (quantized
    collectives: data; pipeline: pipe) the kernel wraps only the rest,
    and nothing when the whole mesh is manual already."""
    from deepspeed_tpu.ops.pallas.common import shard_kernel, split_axes
    from deepspeed_tpu.parallel.topology import build_mesh
    mesh = build_mesh(data=4, model=2)
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

    def body(x):                       # x: this data shard's rows
        spec = P(split_axes(mesh, ("data",), 8),
                 split_axes(mesh, ("model",), x.shape[1]))
        return shard_kernel(lambda t: t * 2.0, mesh, (spec,), spec)(x)

    cols = "model" if "model" in outer else None
    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data", cols),
                       out_specs=P("data", cols), axis_names=set(outer),
                       check_vma=False)
    text = str(jax.make_jaxpr(fn)(x))
    assert text.count("shard_map") == (1 if len(outer) == 2 else 2)
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x)),
                                  np.asarray(x) * 2.0)


def test_engines_own_their_model_config():
    """The engine hands its mesh over ``Model.bind_mesh`` onto the
    model's OWN copy of the config: the caller's object is never written
    and two engines built from one config keep their own mesh."""
    import types
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg = gpt2.GPT2Config(vocab_size=64, max_seq_len=16, n_layers=1,
                          n_heads=2, d_model=16, remat=False)
    before = dataclasses.asdict(cfg)
    conf = {"train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "transformer": {"flash_attention": "xla"},
            "steps_per_print": 10 ** 9}

    def engine(mesh):
        return deepspeed_tpu.initialize(
            model=gpt2.make_gpt2_model(config=cfg),
            mpu=types.SimpleNamespace(mesh=mesh), config_params=conf)[0]

    mesh = build_mesh(data=8)
    one = build_mesh(data=1, devices=jax.devices()[:1])
    first, second = engine(mesh), engine(one)
    assert first.model.config.kernel_mesh is mesh
    assert second.model.config.kernel_mesh is one
    assert first.model.config.flash_attention_backend == "xla"
    assert dataclasses.asdict(cfg) == before and cfg.kernel_mesh is None


# ----------------------------------------- chip_smoke's phases, rehearsed
@pytest.fixture(scope="module")
def smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _tiny_gpt2():
    # every width the phases derive sizes from stays legal: d_head 64
    # (two heads fill the 128 lanes), seq a multiple of the page size
    # and of every prefill bucket
    from deepspeed_tpu.models import gpt2
    return gpt2.GPT2Config(vocab_size=512, max_seq_len=128, n_layers=2,
                           n_heads=2, d_model=128, remat=False,
                           loss_chunk=64)


@pytest.mark.parametrize("phase", ["kernels", "train", "serve",
                                   "four_chips"])
def test_chip_smoke_phase_rehearsal(smoke, phase):
    """The functions ``chip_smoke.main()`` runs on the chip, at a tiny
    config with the kernels under the Pallas interpreter: every check a
    phase makes on the chip (oracle errors, resolved kernels, falling
    loss, stream agreement, byte shares, collectives) is made here too,
    except the ``tpu_custom_call`` count."""
    cfg = _tiny_gpt2()
    if phase == "kernels":
        smoke.phase_kernels(cfg, 0, rehearsal=True)
    elif phase == "train":
        out = smoke.phase_train(cfg, 0, 1, rehearsal=True)
        assert len(out["losses"]) == 8
    elif phase == "serve":
        out = smoke.phase_serve(cfg, 0, rehearsal=True)
        assert out["requests"] == 12
    else:
        out = smoke.phase_four_chips(cfg, 0, jax.devices()[:4],
                                     rehearsal=True)
        assert len(out["losses4"]) == len(out["losses1"]) == 4
