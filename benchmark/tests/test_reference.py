"""The plain reference against the program's ``LlamaForCausalLM`` at a tiny
size on the CPU, float32: logits, loss and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from benchmark.reference import llama_arch
from conftest import TINY


@pytest.fixture(scope="module")
def pair():
    from benchmark.harness.serve import build_model
    model, made = build_model(TINY, seed=2**31 + 11)
    return model, made


def test_weights_are_a_function_of_the_seed(pair):
    _, made = pair
    again = weights.make_weights(TINY, 2**31 + 11, "float32")
    other = weights.make_weights(TINY, 12, "float32")
    assert all(np.array_equal(made[k], again[k]) for k in made)
    assert not np.array_equal(made["head"], other["head"])
    assert abs(float(made["layers.0.in_norm"].mean()) - 1.0) < 0.05


def test_logits_agree(pair):
    model, made = pair
    ids = np.random.default_rng(0).integers(1, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)[None])[0])
    want = np.asarray(llama_arch.logits(made, TINY, ids))
    # float32 on both sides, different association: a few ulps of O(1)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_loss_and_gradients_agree(pair):
    from paddle_tpu.nn.layer import bind_params
    model, made = pair
    rng = np.random.default_rng(1)
    ids = rng.integers(1, 256, 33).astype(np.int32)
    x, y = jnp.asarray(ids[:-1]), jnp.asarray(ids[1:])
    want, g_ref = jax.value_and_grad(
        lambda w: llama_arch.causal_lm_loss(w, TINY, x, y))(made)
    params = model.state_dict(include_buffers=True)

    def loss(p):
        with bind_params(model, p):
            return model.compute_loss(x[None], y[None])

    with jax.default_matmul_precision("highest"):
        got, g = jax.value_and_grad(loss)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in ("head", "norm", "layers.1.down", "layers.1.q",
                 "layers.0.in_norm", "embed"):
        a, b = np.asarray(g[weights.program_name(name)]), np.asarray(
            g_ref[name])
        assert np.abs(a - b).max() <= 1e-5 + 1e-4 * np.abs(b).max(), name


def test_int8_control_moves_the_logits(pair):
    _, made = pair
    ids = np.arange(1, 33, dtype=np.int32)
    a = np.asarray(llama_arch.logits(made, TINY, ids))
    b = np.asarray(llama_arch.logits(made, TINY, ids, weight_bits=8))
    assert 1e-4 < np.abs(a - b).max() < 0.5
