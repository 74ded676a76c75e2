"""``ops/moe.py::expert_ffn`` where the chip holds a SHARE of the experts:
the held rows are compacted to ``share_capacity`` and taken in as many
passes as the router makes necessary, no row dropped; with every expert
held the layer is the one pass over all the rows that it was."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import cohere2_moe, deepseek_v3, lfm2, mellum
from deepspeed_tpu.ops import moe
from deepspeed_tpu.ops.pallas import moe as kernels

KERNELS = pytest.mark.parametrize("kernel", ["xla", "pallas"])
E, HELD, TOP_K = 16, (4, 6), 8        # the share: experts 4 and 5
T, D, FF = 64, 32, 16                 # 512 routed rows; capacity 128


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(seed=0, d=D, ff=FF, experts=E):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return f(T, d), 0.2 * f(experts, d, 2 * ff), 0.2 * f(experts, ff, d)


def _mix(rng, chosen):
    return jnp.asarray(rng.uniform(0.05, 1.0, chosen.shape), jnp.float32)


def _oracle(x, chosen, weights, w13, w2, experts):
    """Every expert of ``experts`` on every token, masked by the choice."""
    ff = w2.shape[1]
    out = jnp.zeros_like(x)
    for e in range(*experts):
        h = x @ w13[e]
        y = (jax.nn.silu(h[:, :ff]) * h[:, ff:]) @ w2[e]
        out += ((chosen == e) * weights).sum(-1, keepdims=True) * y
    return out


def _share(x, chosen, weights, w13, w2, kernel, held=HELD, experts=E):
    a, b = held
    out, load = moe.expert_ffn(x, jnp.asarray(chosen), weights, w13[a:b],
                               w2[a:b], held, experts, kernel=kernel)
    passes = moe.share_passes(load, chosen.size, held, experts)
    return np.asarray(out), np.asarray(load), int(passes)


def _check_load(load, chosen, held=HELD, experts=E):
    rows = np.bincount(np.asarray(chosen).ravel(), minlength=experts)
    rows[:held[0]] = rows[held[1]:] = 0
    np.testing.assert_array_equal(load[0], rows)
    np.testing.assert_array_equal(load[1], rows > 0)


def test_the_capacity_is_the_even_share_doubled_in_row_tiles():
    # command-a-plus-serve.rag: 16 of 128 held, 8 choices a token
    assert [moe.share_capacity(t * 8, 16, 128)
            for t in (2048, 1024, 512, 40)] == [4096, 2048, 1024, 128]
    # every expert held: all the rows, padded, whatever the factor
    assert moe.share_capacity(320, 32, 32) == 384
    assert moe.share_capacity(16384, 64, 64) == 16384
    # fewer rows than a tile: one tile of them all
    assert moe.share_capacity(24, 2, 8) == 24
    assert moe.share_capacity(T * TOP_K, 2, E) == 128


@pytest.mark.pallas
@KERNELS
def test_a_share_under_an_even_router_is_the_oracles_part(kernel):
    """(a) 2 of 16 experts, each token's 8 choices distinct and uniform:
    an eighth of the 512 rows lands here, inside the capacity of 128."""
    x, w13, w2 = _weights()
    rng = np.random.default_rng(1)
    chosen = np.stack([rng.permutation(E)[:TOP_K] for _ in range(T)])
    weights = _mix(rng, chosen)
    got, load, passes = _share(x, chosen, weights, w13, w2, kernel)
    np.testing.assert_allclose(
        got, _oracle(x, chosen, weights, w13, w2, HELD), atol=3e-5)
    _check_load(load, chosen)
    assert 0 < load[0].sum() <= 128 and passes == 1


@pytest.mark.pallas
@KERNELS
@pytest.mark.parametrize("hot", [0.5, 0.75, 1.0],
                         ids=["even", "hot_expert_three_capacities",
                              "one_expert_takes_every_row"])
def test_skew_past_the_capacity_drops_no_row(kernel, hot):
    """(b) every choice of every token is a held expert: 512 held rows,
    four passes at the capacity of 128, one expert taking ``hot`` of
    them (three capacities' worth at 0.75): its group lies across the
    passes, and every row reaches its token."""
    x, w13, w2 = _weights(seed=2)
    rng = np.random.default_rng(3)
    chosen = HELD[0] + (rng.random((T, TOP_K)) >= hot).astype(np.int32)
    weights = _mix(rng, chosen)
    got, load, passes = _share(x, chosen, weights, w13, w2, kernel)
    np.testing.assert_allclose(
        got, _oracle(x, chosen, weights, w13, w2, HELD), atol=1e-4)
    _check_load(load, chosen)
    assert load[0].sum() == T * TOP_K and passes == 4
    if hot == 0.75:
        assert load[0].max() >= 3 * 128


@pytest.mark.pallas
@KERNELS
def test_a_pass_that_is_partly_full_takes_the_rows_there_are(kernel):
    """Between the cases above: 200 held rows, one pass full and one of
    72 rows, the second pass's groups starting inside the first's."""
    x, w13, w2 = _weights(seed=4)
    rng = np.random.default_rng(5)
    chosen = rng.integers(8, E, (T, TOP_K)).astype(np.int32)
    here = rng.permutation(T * TOP_K)[:200]
    chosen.reshape(-1)[here] = HELD[0] + rng.integers(0, 2, 200)
    weights = _mix(rng, chosen)
    got, load, passes = _share(x, chosen, weights, w13, w2, kernel)
    np.testing.assert_allclose(
        got, _oracle(x, chosen, weights, w13, w2, HELD), atol=5e-5)
    _check_load(load, chosen)
    assert load[0].sum() == 200 and passes == 2


@pytest.mark.pallas
@KERNELS
def test_no_row_lands_here(kernel):
    """(c) zeros out, a load of zeros, one pass (over nothing)."""
    x, w13, w2 = _weights(seed=6)
    rng = np.random.default_rng(7)
    chosen = rng.integers(8, E, (T, TOP_K)).astype(np.int32)
    got, load, passes = _share(x, chosen, _mix(rng, chosen), w13, w2, kernel)
    np.testing.assert_array_equal(got, np.zeros_like(got))
    np.testing.assert_array_equal(load, np.zeros_like(load))
    assert passes == 1


@pytest.mark.parametrize("n_held", [0, 37, 128, 200])
def test_nan_past_the_held_rows_reaches_no_token(monkeypatch, n_held):
    """(d) a grouped matmul that leaves NaN in every row past its groups
    (what the kernel's unwritten tiles may hold): the compacted buffer's
    rows past the held ones are selected out, not multiplied by zero."""
    oracle = kernels.moe_gmm_xla

    def poisoned(lhs, rhs, sizes):
        past = jnp.arange(lhs.shape[0])[:, None] >= sizes.sum()
        return jnp.where(past, jnp.nan, oracle(lhs, rhs, sizes))

    monkeypatch.setattr(kernels, "moe_gmm_xla", poisoned)
    x, w13, w2 = _weights(seed=8)
    rng = np.random.default_rng(9)
    chosen = rng.integers(8, E, (T, TOP_K)).astype(np.int32)
    here = rng.permutation(T * TOP_K)[:n_held]
    chosen.reshape(-1)[here] = HELD[0] + rng.integers(0, 2, n_held)
    weights = _mix(rng, chosen)
    got, load, passes = _share(x, chosen, weights, w13, w2, "xla")
    assert np.isfinite(got).all() and load[0].sum() == n_held
    assert passes == max(1, -(-n_held // 128))
    np.testing.assert_allclose(
        got, _oracle(x, chosen, weights, w13, w2, HELD), atol=5e-5)


@pytest.mark.pallas
@KERNELS
def test_the_shares_of_all_holders_past_a_tile_add_up_to_the_layer(kernel):
    """(e) at 512 rows (test_lfm2's share test stands at 48, one tile):
    eight shares of 2, each compacted to 128 rows, against the layer
    with every expert held, which is one pass over the 512."""
    x, w13, w2 = _weights(seed=10)
    rng = np.random.default_rng(11)
    chosen = np.stack([rng.permutation(E)[:TOP_K] for _ in range(T)])
    weights = _mix(rng, chosen)
    whole, whole_load = moe.expert_ffn(x, jnp.asarray(chosen), weights, w13,
                                       w2, (0, E), E, kernel=kernel)
    parts = [_share(x, chosen, weights, w13, w2, kernel, held=(a, a + 2))
             for a in range(0, E, 2)]
    np.testing.assert_allclose(sum(p for p, _, _ in parts), whole, atol=1e-4)
    np.testing.assert_array_equal(sum(l for _, l, _ in parts), whole_load)
    np.testing.assert_allclose(
        whole, _oracle(x, chosen, weights, w13, w2, (0, E)), atol=1e-4)


# ----------------------------------------------------------- the counter
def test_load_attrs_are_what_they_were():
    load = np.zeros((2, 8), np.int32)
    load[0, 2:5] = (7, 0, 30)
    load[1] = load[0] > 0
    assert moe.load_attrs(load) == {"rows": 37, "experts_hit": 2,
                                    "hottest_rows": 30}


@pytest.mark.parametrize("decoder", [
    lfm2.LFM2Decoder, deepseek_v3.DeepseekV3Decoder, mellum.MellumDecoder],
    ids=["lfm2", "deepseek_v3", "mellum"])
def test_every_expert_families_counters_carry_no_passes(decoder):
    """(f) a family that holds every expert makes one pass by
    construction and says nothing of it."""
    load = np.zeros((2, 8), np.int32)
    load[0, :3] = (5, 1, 9)
    load[1] = load[0] > 0
    assert decoder.counter_attrs("moe.load", load) == {
        "rows": 15, "experts_hit": 3, "hottest_rows": 9}


def test_a_share_holders_counters_carry_routed_and_passes():
    """(f) ``cohere2_moe``: the third row of what its programs return
    holds the (token, choice) pairs routed anywhere and the launch's
    expert-layer passes."""
    value = np.zeros((3, 8), np.int32)
    value[0, :2] = (5, 9)
    value[1] = value[0] > 0
    value[2, :2] = (96, 5)
    assert cohere2_moe.Cohere2MoeDecoder.counter_attrs("moe.load", value) \
        == {"rows": 14, "experts_hit": 2, "hottest_rows": 9, "routed": 96,
            "passes": 5}


def _cohere_layer(held, top_k):
    config = cohere2_moe.Cohere2MoeConfig(
        vocab_size=64, d_model=32, layer_types=(cohere2_moe.FULL,),
        n_heads=2, n_kv_heads=1, d_head=16, d_expert=16, n_experts=E,
        top_k=top_k, experts_held=held, n_shared=1, d_shared=16,
        dtype=jnp.float32, moe_kernel="xla")
    return config, cohere2_moe.init_layer(config, 3, 0)


@pytest.mark.parametrize("skewed,passes", [(False, 1), (True, 4)],
                         ids=["router_as_drawn", "router_towards_the_share"])
def test_the_models_expert_layer_counts_its_passes(skewed, passes):
    """``_experts`` writes beside ``routed`` the passes its layer made: 1
    under the router as drawn, 4 where the router's matrix sends both
    choices of every token to the two experts held."""
    config, lp = _cohere_layer((0, 2), top_k=2)
    u = jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, 4 * T, 32)), jnp.float32)                 # 512 routed rows
    if skewed:
        # sigmoid(0) for the held two, sigmoid(-|u| . 1) below it for
        # the others
        lp = dict(lp, router=jnp.zeros_like(lp["router"]).at[:, 2:].set(
            -1.0))
        u = jnp.abs(u)
    out, load = cohere2_moe._experts(u, lp, config)
    assert out.shape == u.shape and load.shape == (3, E)
    assert int(load[2, 0]) == 512 and int(load[2, 1]) == passes
    assert int(load[0].sum()) == (512 if skewed else int(load[0, :2].sum()))
    attrs = cohere2_moe.Cohere2MoeDecoder.counter_attrs("moe.load", load)
    assert attrs["passes"] == passes and attrs["routed"] == 512


# ------------------------------------------- every expert held: the road
# extract's, reasoning's and ide's expert layers in miniature: (experts,
# top_k, a prefill chunk's tokens, a decode step's slots)
FAMILIES = {"extract": (32, 4, 64, 48), "reasoning": (64, 6, 128, 40),
            "ide": (64, 8, 128, 16)}


def _primitives(jaxpr, found=None):
    """Every equation of ``jaxpr`` and of what it calls, a kernel's body
    apart (its ``pl.when`` is a ``cond`` of the kernel's own)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _primitives(sub, found)
    return found


def _trace(tokens, experts, top_k, held, d=128, ff=128):
    a, b = held
    args = (jnp.zeros((tokens, d), jnp.bfloat16),
            jnp.zeros((tokens, top_k), jnp.int32),
            jnp.zeros((tokens, top_k), jnp.float32),
            jnp.zeros((b - a, d, 2 * ff), jnp.bfloat16),
            jnp.zeros((b - a, ff, d), jnp.bfloat16))
    return _primitives(jax.make_jaxpr(
        lambda *xs: moe.expert_ffn(*xs, held, experts, kernel="pallas"))(
            *args).jaxpr)


@pytest.mark.pallas
@pytest.mark.parametrize("tokens_of", [2, 3], ids=["chunk", "decode_step"])
@pytest.mark.parametrize("cell", sorted(FAMILIES))
def test_every_expert_held_is_the_road_it_was(cell, tokens_of):
    """No loop, no branch, no scatter-add; both ``moe_gmm`` calls over
    all ``T x k`` rows, padded, with ``m // tm + E - 1`` item slots."""
    experts, top_k = FAMILIES[cell][:2]
    tokens = FAMILIES[cell][tokens_of]
    eqns = _trace(tokens, experts, top_k, (0, experts))
    names = [e.primitive.name for e in eqns]
    assert not {"while", "cond", "scatter-add", "scatter_add"} & set(names)
    rows = tokens * top_k
    tm = kernels.row_tile(rows)
    padded = -(-rows // tm) * tm
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    for call in calls:
        group, tile, _, _, _, lhs, rhs = [v.aval for v in call.invars]
        assert lhs.shape[0] == padded and rhs.shape[0] == experts
        assert group.shape == tile.shape == (padded // tm + experts - 1,)
        assert call.params["grid_mapping"].grid[1] == group.shape[0]


@pytest.mark.pallas
def test_a_share_is_a_loop_over_its_capacity():
    """The other side of the same switch, at rag's share in miniature:
    16 of 128 held. A ``while`` around one jitted pass (two layers of a
    program trace and lower it once): its ``moe_gmm`` calls over 256 of
    the 1,024 rows with 2 + 15 item slots, its combine one scatter-add."""
    eqns = _trace(128, 128, 8, (0, 16))
    names = [e.primitive.name for e in eqns]
    assert names.count("while") == 1 and "cond" not in names
    passes = [e for e in eqns if e.primitive.name == "jit" and
              e.params["name"] == "_share_pass"]
    assert len(passes) == 1
    again = [e for e in _trace(128, 128, 8, (0, 16))
             if e.primitive.name == "jit" and
             e.params["name"] == "_share_pass"]
    assert again[0].params["jaxpr"] is passes[0].params["jaxpr"]
    inner = [e.primitive.name for e in _primitives(
        passes[0].params["jaxpr"].jaxpr)]
    assert sum(n in ("scatter-add", "scatter_add") for n in inner) == 1
    assert sum(n in ("scatter-add", "scatter_add") for n in names) == 1
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    for call in calls:
        group, _, _, _, _, lhs, rhs = [v.aval for v in call.invars]
        assert lhs.shape[0] == 256 and rhs.shape[0] == 16
        assert group.shape == (256 // 128 + 16 - 1,)
