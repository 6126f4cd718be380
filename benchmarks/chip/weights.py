"""Seeded weights for a configuration, made on the device in one call.

The benchmark makes the weights, not the program, so that the plain
reference can read the same numbers without taking anything the program
made.  The configuration's family (``spec.family``) draws the tree in
the layout of the program's ``model.init`` (the system under test takes
it as its parameters), in the published arithmetic, from ``key_for``:
every value a bf16 number stored in float32 (the program's parameter
type), so the serving step's bf16 cast loses nothing, and the
vocabulary's padding rows and columns zero.  ``published`` gives the
same numbers in the published layout, for the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import spec


def key_for(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer seed (wider than 32
    bits included), so two seeds never share a key."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make_params(conf, seed: int):
    """The program's parameters for configuration ``conf``, drawn from
    ``seed``."""
    return spec.family(conf).make_params(conf, seed)


def published(conf, params):
    """``params`` in the published layout of configuration ``conf``."""
    return spec.family(conf).published(params, conf)
