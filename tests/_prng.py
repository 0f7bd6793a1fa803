"""Which threefry bit layout ``jax.random`` uses in this process.

Golden token streams depend on the weights ``lm.init_params`` draws, and
those depend on ``jax_threefry_partitionable``: on by default since JAX
0.5, off in older captures and wherever an environment turns it off. The
goldens are stored per layout and a test reads the one in effect."""
import jax


def prng_layout() -> str:
    return ("partitionable" if jax.config.jax_threefry_partitionable
            else "legacy")
