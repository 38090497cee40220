"""Weights from the seed, made by the benchmark and given to both sides.

The program under test and the plain reference each get the same values:
one jitted call makes every leaf on the device, in the type it is served
in (bfloat16).  The rule is the published initialisation of both model
families: Normal(0, initializer_range) on every matrix, ones on a
norm's weight, zeros on every bias.
"""

from __future__ import annotations

import numpy as np


def key_of(seed: int, stream: int = 0):
    """A jax PRNG key from any whole-number seed (the driver's seeds pass
    2**31, more than a signed 32-bit jax int holds)."""
    import jax

    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def init_kind(name: str, shape) -> str:
    if len(shape) >= 2:
        return "normal"
    return "ones" if name.endswith("weight") else "zeros"


def make_leaf(key, index: int, name: str, shape, dtype, std: float):
    import jax
    import jax.numpy as jnp

    kind = init_kind(name, shape)
    if kind == "normal":
        k = jax.random.fold_in(key, index)
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    fill = 1.0 if kind == "ones" else 0.0
    return jnp.full(shape, fill, dtype)


def make_weights(spec: dict, seed: int, dtype="bfloat16", std: float = 0.02,
                 names=None):
    """``spec`` maps a leaf's name to its shape.  Every leaf's value
    depends only on the seed and the leaf's place in the sorted names, so
    a subset (``names``) can be made again alone: the reference does that
    layer by layer."""
    import jax

    order = {n: i for i, n in enumerate(sorted(spec))}
    want = sorted(spec) if names is None else list(names)

    def gen(key):
        return {n: make_leaf(key, order[n], n, tuple(spec[n]), dtype, std)
                for n in want}

    return jax.jit(gen)(key_of(seed, 0))
