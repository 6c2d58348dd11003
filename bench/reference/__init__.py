"""Plain references the benchmark compares the program with; nothing here
imports the program."""

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (more than 32 bits may be given)."""
    words = np.random.SeedSequence(int(seed) % 2**64).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")
