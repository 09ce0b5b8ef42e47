"""The contract between a model and the serving engine
(``models.parts.ServingTraits``), over the five served families at their
tiny configs: what the per-family sweeps
(``test_unsupported_layouts_refuse_by_name`` and its kin) do not hold — the
record's type, the model's own reason in every refusal, the layouts a family
does NOT list, llama's defaults, and a key the engine does not know."""

import dataclasses
import functools

import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models.parts import ServingTraits
from paddle_tpu.serving import ServingEngine

# family -> (class, tiny config, engine arguments of a layout it runs)
_CHUNKED = dict(paged=True, chunked=True, prefill_chunk=8, block_len=8)
FAMILIES = {
    "llama": (models.LlamaForCausalLM, models.tiny_llama_config, {}),
    "afmoe": (models.AfmoeForCausalLM, models.tiny_afmoe_config,
              dict(paged=True, block_len=8)),
    "lfm2": (models.Lfm2MoeForCausalLM, models.tiny_lfm2_config,
             dict(_CHUNKED, prefix_cache=False)),
    "sdar": (models.SdarMoeForCausalLM, models.tiny_sdar_config,
             dict(_CHUNKED, prefix_cache=False)),
    "latent_moe": (models.LatentMoeForCausalLM, models.tiny_latent_moe_config,
                   dict(_CHUNKED, prefix_cache=False)),
}
# layout key -> the engine arguments that ask for it
ASKS = {
    "contiguous_cache": dict(paged=False),
    "wave_prefill": dict(chunked=False),
    "prefix_cache": dict(prefix_cache=True),
    "preemption": dict(preempt="recompute"),
    "kv_cache_dtype": dict(kv_cache_dtype="int8"),
    "mesh": dict(mesh="mp2"),
    "spec_decode": dict(spec_decode=True),
    "int8_weights": dict(int8_weights=True),
}


@functools.lru_cache(maxsize=None)
def _model(family):
    cls, tiny, _ = FAMILIES[family]
    pt.seed(0)
    model = cls(tiny())
    model.eval()
    return model


def _engine(family, **over):
    kw = dict(num_slots=2, max_length=64, **FAMILIES[family][2])
    return ServingEngine(_model(family), **{**kw, **over})


def _traits(family):
    return getattr(_model(family), "serving_traits", ServingTraits())


def _refusals():
    for family in FAMILIES:
        for layout in _traits(family).unsupported:
            yield family, layout


@pytest.mark.parametrize("family", list(FAMILIES))
def test_traits_are_the_one_frozen_record(family):
    traits = _traits(family)
    assert type(traits) is ServingTraits
    assert set(traits.unsupported) <= set(ASKS)
    with pytest.raises(dataclasses.FrozenInstanceError):
        traits.expert_layers = 1
    # the record is all the engine asks: none of the names it read before
    for old in ("kv_pool_entry", "block_diffusion", "slot_state",
                "expert_layers", "attention_windows", "serving_kernel_specs",
                "check_serving_layout"):
        assert not hasattr(_model(family), old), old


@pytest.mark.parametrize("family, layout", list(_refusals()))
def test_a_refusal_names_the_class_and_gives_the_models_reason(family,
                                                               layout):
    why = _traits(family).unsupported[layout]
    with pytest.raises(NotImplementedError) as e:
        _engine(family, **ASKS[layout])
    said = str(e.value)
    assert said.startswith(
        f"{type(_model(family)).__name__} cannot be served with ")
    assert said.endswith(": " + why)


@pytest.mark.parametrize("family, layout", [
    ("afmoe", "wave_prefill"), ("afmoe", "prefix_cache"),
    ("latent_moe", "prefix_cache")])
def test_a_layout_a_family_does_not_list_constructs(family, layout):
    assert layout not in _traits(family).unsupported
    eng = _engine(family, **ASKS[layout])
    if layout == "wave_prefill":
        assert not eng.chunked and eng._prefill_fn is not None
    else:
        assert eng.kv.prefix_cache


@pytest.mark.parametrize("layout", list(ASKS))
def test_llamas_defaults_refuse_nothing(layout):
    assert not hasattr(_model("llama"), "serving_traits")
    assert ServingTraits().unsupported == {}
    base = {} if layout == "contiguous_cache" else dict(paged=True,
                                                        block_len=8)
    eng = _engine("llama", **base, **ASKS[layout])
    assert (eng._pool_entry, eng._block, eng._slot_leaves,
            eng._expert_layers, eng._windows) == (None, 0, (), 0, ())


def test_an_unknown_unsupported_key_fails_at_construction(monkeypatch):
    model = _model("afmoe")
    traits = dataclasses.replace(
        model.serving_traits,
        unsupported={"contiguous_cache": "why", "no_such_layout": "why"})
    monkeypatch.setattr(type(model), "serving_traits", traits)
    with pytest.raises(ValueError, match="no_such_layout"):
        _engine("afmoe")
