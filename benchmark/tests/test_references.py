"""Each plain reference against the program's own forward at tiny size,
on the same weights from the seed."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config

from benchmark import weights
from benchmark.reference import common


@pytest.mark.parametrize("name", ["mistral-7b", "gpt3-6.7b"])
def test_reference_matches_program_forward(name):
    from paddle_tpu.nn.layer import functional_call, raw_params

    config = tiny_config(name)
    builder = importlib.import_module("benchmark.builders." + config["builder"])
    family = importlib.import_module("benchmark.reference."
                                     + config["reference"])
    model = builder.build_model(config, 2, 64)
    shapes = {k: tuple(v.shape) for k, v in raw_params(model).items()}
    assert shapes == family.param_shapes(config, 2)
    params = weights.make_weights(shapes, 2**31 + 3, dtype="float32")
    ids = np.random.default_rng(0).integers(0, config["vocab_size"], (2, 48))
    got = functional_call(model, params, jnp.asarray(ids), training=False)
    for row in range(2):
        ref = common.sequence_logits(family, params, jnp.asarray(ids[row]),
                                     config, common.Precision("f32"), 2)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


def test_weights_same_seed_same_values_and_subsets():
    shapes = {"a.weight": (8, 4), "b.weight": (4,), "b.bias": (4,),
              "c.weight": (16, 8)}
    w1 = weights.make_weights(shapes, 2**31 + 11)
    w2 = weights.make_weights(shapes, 2**31 + 11)
    w3 = weights.make_weights(shapes, 2**31 + 12)
    for k in shapes:
        assert np.array_equal(np.asarray(w1[k], np.float32),
                              np.asarray(w2[k], np.float32))
    assert not np.array_equal(np.asarray(w1["a.weight"], np.float32),
                              np.asarray(w3["a.weight"], np.float32))
    assert float(w1["b.weight"][0]) == 1.0 and float(w1["b.bias"][0]) == 0.0
    only = weights.make_weights(shapes, 2**31 + 11, names=["c.weight"])
    assert list(only) == ["c.weight"]
    assert np.array_equal(np.asarray(only["c.weight"], np.float32),
                          np.asarray(w1["c.weight"], np.float32))


def test_precisions_order():
    """The control's rounding is coarser than the stated precision's."""
    x = jax.random.normal(jax.random.key(0), (64, 64))
    w = jax.random.normal(jax.random.key(1), (64, 64))
    exact = common.Precision("f32").mm(x, w)
    e_bf16 = float(jnp.max(jnp.abs(common.Precision("bf16").mm(x, w) - exact)))
    e_fp8 = float(jnp.max(jnp.abs(common.Precision("fp8").mm(x, w) - exact)))
    assert 0 < e_bf16 < e_fp8 / 3
