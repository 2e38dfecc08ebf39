"""What both references share."""

import jax.numpy as jnp


def round_to(x, lowp: bool):
    """``x`` as is, or rounded to bfloat16 and back (the control's
    precision: float32 sums of bfloat16 factors)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if lowp else x


def logloss(z, y):
    """log(1 + e^z) - y z, stably."""
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z))) - y * z
