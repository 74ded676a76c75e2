"""What a cache kind cannot serve is declared once
(``inference/decoder.py``: ``FEATURES`` and ``refuse``), and the
contiguous cache has one user.

The matrix below is what the engine raised, case by case, when each
cache kind had a block of refusals of its own in ``InferenceEngine``
(the tree before PR 48): nothing that was refused is allowed and
nothing that was allowed is refused.
"""
import ast
import json
import os

import pytest

import deepspeed_tpu as deepspeed
from benchmark import manifest
from deepspeed_tpu.inference import decoder

pytestmark = pytest.mark.serving

_TINY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "unit_benchmark")

# cache kind -> the tiny configuration of a family that keeps it
KINDS = {
    "keys_and_values": "tiny/configs/tiny-serve.json",        # GPT-2
    "recurrent": "tiny_jamba/configs/tiny-jamba.json",
    "latent": "tiny_moonlight/configs/tiny-moonlight.json",
    "windowed_groups": "tiny_mellum2/configs/tiny-mellum2.json",
}
# feature -> what turns it on in the ``inference`` section
FEATURES = {
    "prefix_caching": {"prefix_caching": True},
    "speculative": {"speculative": {"enabled": True, "method": "ngram",
                                    "num_draft_tokens": 2}},
    "handoff": {"fleet": {"role": "prefill"}},
}
RECURRENT = "recurrent layers: its state is not in the pages"
LATENT = "latent pages: its page rows are not keys and values"
WINDOWED = "sliding-window layers or several page groups: a page is " \
    "not the whole of a position's state in every layer"
# (feature, kind) -> the end of the sentence; absent: served
REFUSED = {
    ("prefix_caching", "recurrent"): RECURRENT,
    ("prefix_caching", "windowed_groups"): WINDOWED,
    ("speculative", "recurrent"): RECURRENT,
    ("speculative", "latent"): LATENT,
    ("speculative", "windowed_groups"): WINDOWED,
    ("handoff", "recurrent"): RECURRENT,
    ("handoff", "latent"): LATENT,
    ("handoff", "windowed_groups"): WINDOWED,
}
SAID = {
    "prefix_caching": "prefix caching (inference.prefix_caching)",
    "speculative": "speculative decoding (inference.speculative)",
    "handoff": "the fleet's page hand-off (inference.fleet)",
}


def _engine(kind, **inference):
    with open(os.path.join(_TINY, KINDS[kind])) as f:
        config = json.load(f)
    config["inference"].update(inference)
    return manifest.plugin("models", config["family"]).build_serve_engine(
        config, 1)


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("feature", sorted(FEATURES))
def test_refusal_matrix(feature, kind):
    why = REFUSED.get((feature, kind))
    if why is None:
        engine = _engine(kind, **FEATURES[feature])
        assert engine.allocator.pages_in_use == 0
        return
    with pytest.raises(ValueError) as raised:
        _engine(kind, **FEATURES[feature])
    assert str(raised.value) == "{} cannot serve a model with {}".format(
        SAID[feature], why)


def test_the_table_is_the_matrix():
    """Every feature of the table is a case above (the drafter's cache
    apart, which judges the DRAFT model), under the name the sentence
    uses."""
    assert set(decoder.FEATURES) == set(FEATURES) | {"draft_cache"}
    for feature, said in SAID.items():
        assert decoder.FEATURES[feature][0] == said


@pytest.mark.parametrize("role", ["PrefillRole", "DecodeRole"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_fleet_role_refuses_what_the_hand_off_refuses(kind, role):
    """A role built on an engine whose config names no role asks the
    same question of the same table."""
    from deepspeed_tpu.inference.fleet import roles
    engine = _engine(kind)
    why = REFUSED.get(("handoff", kind))
    if why is None:
        getattr(roles, role)(engine)
        return
    with pytest.raises(ValueError, match="hand-off .* cannot serve a "
                       "model with " + why.split(":")[0]):
        getattr(roles, role)(engine)


def test_a_draft_model_keeps_keys_and_values_or_is_refused():
    """The model drafter's contiguous cache holds keys and values of a
    model whose pages would be its whole state: the draft model's own
    decoder is what the table is asked about."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.speculative import ModelDrafter
    from deepspeed_tpu.models import gpt2
    ModelDrafter(gpt2.make_gpt2_model(config=gpt2.GPT2Config(
        vocab_size=64, max_seq_len=32, n_layers=1, n_heads=2, d_model=16,
        use_flash_attention=False, remat=False)), 2, 32, jnp.float32)
    for kind, model in (("recurrent", "recurrent layers"),
                        ("latent", "latent pages")):
        draft = _engine(kind).module
        with pytest.raises(ValueError, match="a draft model's contiguous "
                           "cache cannot serve a model with " + model):
            ModelDrafter(draft, 2, 32, jnp.float32)


def _names(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            yield node.id
        elif isinstance(node, ast.Attribute):
            yield node.attr
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                yield alias.name.rsplit(".", 1)[-1]


@pytest.mark.parametrize("name, users", [
    # the contiguous cache: the model drafter's, and nobody else's (it
    # was a serving layout until PR 48 and must not quietly become one)
    ("KVCache", {"inference/speculative.py"}),
    ("KV_CACHE_SPEC", set()),
    # its attention: reached through forward_hidden without page tables
    ("_cached_attn_ctx", set()),
])
def test_the_contiguous_cache_has_one_user(name, users):
    """``name`` is used (in code: docstrings and the lazy export table
    of ``inference/__init__.py`` are strings) by ``users`` alone, the
    module that defines it apart."""
    home = {"KVCache": "inference/kv_cache.py",
            "KV_CACHE_SPEC": "inference/kv_cache.py",
            "_cached_attn_ctx": "models/gpt2.py"}[name]
    root = os.path.dirname(deepspeed.__file__)
    found = set()
    for folder, _, files in os.walk(root):
        for file in files:
            if not file.endswith(".py"):
                continue
            path = os.path.join(folder, file)
            with open(path) as f:
                if name in set(_names(ast.parse(f.read()))):
                    found.add(os.path.relpath(path, root))
    assert found - {home} == users


def test_the_engine_has_no_block_a_cache_kind():
    """``inference/engine.py`` imports one refusal function, and names
    no cache kind to refuse."""
    path = os.path.join(os.path.dirname(deepspeed.__file__), "inference",
                        "engine.py")
    with open(path) as f:
        source = f.read()
    imported = [alias.name for node in ast.walk(ast.parse(source))
                if isinstance(node, ast.ImportFrom)
                and node.module == "decoder" for alias in node.names]
    assert sorted(imported) == ["decoder_of", "refuse"]
    assert "kv_layout" not in source
    for word in ("refuse_", "page_lanes is", "one_table"):
        assert word not in source, word
