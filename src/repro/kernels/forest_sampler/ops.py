"""Public wrapper for the counter-hash draw kernel.

Compiled on TPU, interpret elsewhere — except that under interpret the
per-element pallas emulation is pure overhead, so off-TPU the default is
the jnp reference path (identical arithmetic — both call the same
``mix64_pair``/``mod64_pair``; ``use_kernel=True`` forces the pallas_call
for interpret-equality tests).
"""
from __future__ import annotations

import jax

from repro.core import platform
from repro.kernels.forest_sampler.forest_sampler import (hash_draws,
                                                         hash_draws_ref,
                                                         split64)

__all__ = ["counter_draws", "hash_draws", "hash_draws_ref", "split64"]


def counter_draws(z_hi, z_lo, deg, use_kernel=None) -> jax.Array:
    """(T, L) int32 draws ``mix64(z) mod deg`` — kernel on TPU, jnp off."""
    if use_kernel is None:
        use_kernel = platform.on_tpu()
    if use_kernel:
        return hash_draws(z_hi, z_lo, deg)
    return hash_draws_ref(z_hi, z_lo, deg)
