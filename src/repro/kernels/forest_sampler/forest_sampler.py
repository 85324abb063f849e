"""Counter-hash draw kernel — the forest sampler's splitmix64 on device.

The serving data plane's forest sampler (``repro.sparse.sampler
.sample_forest``) draws neighbor ``r = mix64(key ⊕ tree·C₁ ⊕ hop·C₂ ⊕
lane·C₃) mod deg`` — pure counter arithmetic, no state, no rejection.  That
makes it portable to the accelerator verbatim *except* that TPUs have no
64-bit integers.  This module emulates uint64 as ``(hi, lo)`` uint32 pairs:

* xor splits componentwise (carry-free) — so the whole counter combine
  ``key ⊕ tree·C₁ ⊕ hop·C₂ ⊕ lane·C₃`` is splittable term by term and the
  constant terms precompute host-side (``repro.serve.device_sampler``);
* add-with-carry: ``carry = (lo + b_lo) < lo`` (wrap detection);
* 64-bit multiply mod 2⁶⁴ from 16-bit limb products (every partial product
  fits uint32; the true high word < 2³² so wrapping adds stay exact);
* right-shift-xor with shift < 32 mixes ``hi`` into ``lo``;
* ``mod d`` (d < 2³¹) via ``(hi mod d)`` folded down 32 doublings —
  ``2³² mod d`` computed as iterated ``(2t) mod d`` keeps every
  intermediate < 2³², no widening needed.

``mix64_pair``/``mod64_pair`` are shared by the Pallas kernel body and the
pure-jnp reference path (``hash_draws_ref``) — identical arithmetic by
construction, so kernel == jnp == host-numpy exactly, which the serving
parity anchor (device-sampled dispatch vs host-sampled offline replay,
≤1e-5) silently re-verifies end-to-end on every benchmark run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import platform

_SM_GAMMA = 0x9E3779B97F4A7C15
_SM_M1 = 0xBF58476D1CE4E5B9
_SM_M2 = 0x94D049BB133111EB

_MASK16 = 0xFFFF


def split64(x) -> tuple:
    """Host-side helper: uint64 ndarray → (hi, lo) uint32 pair."""
    import numpy as np
    x = np.asarray(x, np.uint64)
    return ((x >> np.uint64(32)).astype(np.uint32),
            (x & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _u32(v: int):
    return jnp.uint32(v & 0xFFFFFFFF)


def _add64(ahi, alo, bhi, blo):
    lo = alo + blo
    carry = (lo < alo).astype(jnp.uint32)
    return ahi + bhi + carry, lo


def _mul32_wide(a, b):
    """Full 64-bit product of two uint32 as (hi, lo), via 16-bit limbs."""
    a0, a1 = a & _MASK16, a >> 16
    b0, b1 = b & _MASK16, b >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> 16) + (p01 & _MASK16) + (p10 & _MASK16)
    lo = (p00 & _MASK16) | ((mid & _MASK16) << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


def _mul64(ahi, alo, bhi, blo):
    """(a · b) mod 2⁶⁴ on (hi, lo) pairs — cross terms land in hi only."""
    hi, lo = _mul32_wide(alo, blo)
    return hi + alo * bhi + ahi * blo, lo


def _shr_xor64(hi, lo, k: int):
    """(hi, lo) ^ ((hi, lo) >> k), for 0 < k < 32."""
    slo = (lo >> k) | (hi << (32 - k))
    return hi ^ (hi >> k), lo ^ slo


def mix64_pair(hi, lo):
    """splitmix64 finalizer on (hi, lo) uint32 pairs — bit-identical to
    ``repro.sparse.sampler._mix64`` on the packed uint64."""
    hi = hi.astype(jnp.uint32)
    lo = lo.astype(jnp.uint32)
    hi, lo = _add64(hi, lo, _u32(_SM_GAMMA >> 32), _u32(_SM_GAMMA))
    hi, lo = _shr_xor64(hi, lo, 30)
    hi, lo = _mul64(hi, lo, _u32(_SM_M1 >> 32), _u32(_SM_M1))
    hi, lo = _shr_xor64(hi, lo, 27)
    hi, lo = _mul64(hi, lo, _u32(_SM_M2 >> 32), _u32(_SM_M2))
    hi, lo = _shr_xor64(hi, lo, 31)
    return hi, lo


def mod64_pair(hi, lo, d):
    """((hi·2³² + lo) mod d) for uint32 d with 1 ≤ d < 2³¹.

    ``hi mod d`` is folded down by 32 doublings (each ``2t mod d`` stays
    below 2³² because t < d < 2³¹); then one modular add of ``lo mod d``.
    """
    d = d.astype(jnp.uint32)
    t = hi.astype(jnp.uint32) % d
    t = jax.lax.fori_loop(0, 32, lambda i, tt: (tt + tt) % d, t)
    return (t + lo.astype(jnp.uint32) % d) % d


# ---------------------------------------------------------------------------
# Pallas kernel + jnp reference
# ---------------------------------------------------------------------------

def _draws_kernel(zhi_ref, zlo_ref, deg_ref, r_ref):
    hi, lo = mix64_pair(zhi_ref[...], zlo_ref[...])
    r_ref[...] = mod64_pair(hi, lo, deg_ref[...]).astype(jnp.int32)


def hash_draws_ref(z_hi, z_lo, deg):
    """Pure-jnp reference: same pair arithmetic, no pallas_call."""
    hi, lo = mix64_pair(z_hi, z_lo)
    return mod64_pair(hi, lo, deg).astype(jnp.int32)


@platform.resolves_interpret
@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_draws(z_hi: jax.Array, z_lo: jax.Array, deg: jax.Array, *,
               interpret: bool) -> jax.Array:
    """``mix64(z) mod deg`` over a (T, L) counter grid → int32 draws.

    z_hi/z_lo: (T, L) uint32 halves of the combined counter; deg: (T, L)
    uint32 moduli (callers pass ``max(degree, 1)``).  The arrays are padded
    to the 32-bit VMEM tile (8, 128) and run as one whole-array grid step —
    the draw grid for a serving bucket is a few thousand lanes, far under
    VMEM limits.  ``interpret`` follows ``core.platform.interpret_mode``.
    """
    t, l = z_hi.shape
    pt, plm = (-t) % 8, (-l) % 128
    if pt or plm:
        pad = ((0, pt), (0, plm))
        z_hi = jnp.pad(z_hi, pad)
        z_lo = jnp.pad(z_lo, pad)
        deg = jnp.pad(deg, pad, constant_values=1)
    r = pl.pallas_call(
        _draws_kernel,
        out_shape=jax.ShapeDtypeStruct(z_hi.shape, jnp.int32),
        interpret=interpret,
    )(z_hi, z_lo, deg.astype(jnp.uint32))
    return r[:t, :l] if (pt or plm) else r
