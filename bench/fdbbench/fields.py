"""Source fields, made on the device from the seed in one jitted call.

The harmonics are those of ``repro.fields.synthetic_field`` (a few random
low-order zonal and meridional modes, normalised, around a per-parameter
base and scale), drawn with ``jax.random`` instead of NumPy so that a pool
of global fields costs one device program rather than minutes of host
work.  The pool is pulled to the host once and requests draw from it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_pool", "seed_key"]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low and high 32 bits go in
    separately, so seeds beyond 32 bits neither overflow nor collide."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


@partial(jax.jit, static_argnames=("grid", "n_modes"))
def _harmonics(key, base, scale, *, grid, n_modes=6):
    n = base.shape[0]
    h, w = grid
    kk, km, ka, kp = jax.random.split(key, 4)
    k = jax.random.randint(kk, (n, n_modes), 1, 6).astype(jnp.float32)
    m = jax.random.randint(km, (n, n_modes), 0, 5).astype(jnp.float32)
    amp = jax.random.normal(ka, (n, n_modes)) / (1.0 + k + m)
    phase = jax.random.uniform(kp, (n, n_modes), maxval=2 * math.pi)
    lat = jnp.linspace(-math.pi / 2, math.pi / 2, h)[:, None]
    lon = jnp.linspace(0.0, 2 * math.pi, w, endpoint=False)[None, :]
    # cos(pi/2) rounds below zero in float32; a negative base to a real
    # power is NaN
    coslat = jnp.clip(jnp.cos(lat), 0.0, 1.0)
    f = jnp.zeros((n, h, w), jnp.float32)
    for j in range(n_modes):
        c = lambda a: a[:, j, None, None]  # noqa: E731
        f = f + c(amp) * jnp.cos(c(m) * lon + c(phase)) * coslat ** c(k)
    f = f / jnp.maximum(f.std(axis=(1, 2), keepdims=True), 1e-9)
    return base[:, None, None] + scale[:, None, None] * f


def make_pool(seed: int, bases, scales, grid: tuple[int, int]) -> np.ndarray:
    """``(len(bases), H, W)`` float32 fields on the host, field ``i`` around
    ``bases[i]`` with spread ``scales[i]``; the same seed gives the same
    pool."""
    out = _harmonics(seed_key(seed), jnp.asarray(bases, jnp.float32),
                     jnp.asarray(scales, jnp.float32), grid=tuple(grid))
    return np.asarray(out)
