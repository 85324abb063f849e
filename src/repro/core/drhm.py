"""Dynamic Reseeding Hash-based Mapping (DRHM) — paper §3.5, Eq. (3)/(4).

The paper maps partial-product TAGs onto NeuraMem units with

    H_l(TAG, gamma) = ((TAG << k) >> k) * gamma  mod N          (lower-k bits)
    H_h(TAG, gamma) = ((TAG >> k) << k) * gamma  mod N          (upper-k bits)

reseeding ``gamma`` after every computed row so no sparsity pattern can pin a
hot spot onto one unit.  The paper selects the lower-k variant (fewer
collisions, §3.5), and so do we.

At pod scale the same function becomes the *ownership* map: which device owns
a destination row / embedding row / expert slot.  Two requirements from paper
§2.4 carry over verbatim — consistency (same id → same owner within a round)
and sparsity-agnostic uniformity.  We add a third that the ASIC did not need:
**bijectivity** over padded power-of-two domains (odd multiplier modulo 2^m),
so the map can also be used as a cheap permutation with an exact inverse
(needed to reshard checkpoints and to undo dispatch).

Mapping variants ``ring`` / ``modular`` / ``random`` are kept for the paper's
Figure 12/13 comparison benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_MERSENNE_PRIME = (1 << 31) - 1


def reseed(key: jax.Array) -> Array:
    """Draw a fresh odd gamma (odd ⇒ bijective mod any power of two)."""
    g = jax.random.randint(key, (), minval=1, maxval=2**30, dtype=jnp.int32)
    return (g * 2 + 1).astype(jnp.uint32)


def drhm_hash(tags: Array, gamma: Array, n_bins: int, k: int = 16) -> Array:
    """Lower-k-bit DRHM hash (paper Eq. 3), high-bits variant.

    Eq. 3 as literally written — ``(low_k(TAG)·γ) mod N`` — degenerates when
    N is a power of two and TAGs share a power-of-two stride (the product's
    low bits are then constant), so we take the product's HIGH bits instead
    (Fibonacci multiplicative hashing).  Same hardware structure — one
    reseeded multiplier — with actual mixing; deviation noted in DESIGN.md §8.
    """
    t = tags.astype(jnp.uint32) & jnp.uint32((1 << k) - 1)
    prod = t * gamma.astype(jnp.uint32)
    shift = 32 - max(1, int(np.ceil(np.log2(max(n_bins, 2)))))
    return ((prod >> jnp.uint32(shift)) % jnp.uint32(n_bins)).astype(jnp.int32)


def drhm_hash_upper(tags: Array, gamma: Array, n_bins: int, k: int = 16) -> Array:
    """Upper-k-bit DRHM hash (paper Eq. 4) — kept for the design-space study."""
    t = (tags.astype(jnp.uint32) >> jnp.uint32(k)) << jnp.uint32(k)
    return ((t * gamma.astype(jnp.uint32)) % jnp.uint32(n_bins)).astype(jnp.int32)


def drhm_permutation(n: int, gamma: int) -> np.ndarray:
    """Bijective DRHM permutation of [0, n): requires gcd(gamma, n) == 1.

    perm[i] = (i * gamma) mod n.  Host-side (used by shard planners).
    """
    import math
    assert math.gcd(n, gamma) == 1, f"gamma {gamma} not coprime to {n}"
    idx = np.arange(n, dtype=np.uint64)
    return ((idx * np.uint64(gamma)) % np.uint64(n)).astype(np.int64)


_GAMMA_PRIMES = (2654435761, 40503, 2246822519, 3266489917, 668265263)


def coprime_gamma(n: int, seed: int = 0) -> int:
    """Pick a large multiplier coprime to n (bijectivity for any pad size)."""
    import math
    for i in range(len(_GAMMA_PRIMES)):
        g = _GAMMA_PRIMES[(seed + i) % len(_GAMMA_PRIMES)] | 1
        if math.gcd(n, g) == 1:
            return g
    g = 3
    while math.gcd(n, g) != 1:
        g += 2
    return g


def invert_permutation(perm: np.ndarray) -> np.ndarray:
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.shape[0], dtype=perm.dtype)
    return inv


# ---------------------------------------------------------------------------
# Mapping variants for the paper's Figure 12/13 comparison
# ---------------------------------------------------------------------------

def ring_map(tags: Array, n_bins: int, **_) -> Array:
    """Round-robin / ring mapping (paper: Takenaka et al.)."""
    return (tags % n_bins).astype(jnp.int32)


def modular_map(tags: Array, n_bins: int, prime: int = 2654435761, **_) -> Array:
    """Prime-multiplier modular hashing (paper: Bhullar et al.) — fixed seed."""
    t = tags.astype(jnp.uint32) * jnp.uint32(prime % (1 << 32))
    return (t % jnp.uint32(n_bins)).astype(jnp.int32)


def random_map(tags: Array, n_bins: int, lookup: Array = None, **_) -> Array:
    """Ideal random mapping via an explicit lookup table (impractical on ASIC —
    the paper's strawman; we materialize it for benchmarking only)."""
    assert lookup is not None, "random_map requires a lookup table"
    return lookup[tags]


def drhm_map(tags: Array, n_bins: int, gamma: Array = None, k: int = 16, **_) -> Array:
    assert gamma is not None
    return drhm_hash(tags, gamma, n_bins, k=k)


MAPPINGS: Dict[str, Callable] = {
    "ring": ring_map,
    "modular": modular_map,
    "random": random_map,
    "drhm": drhm_map,
}


# ---------------------------------------------------------------------------
# Balance statistics (hot-spot metrics for Fig 12/13 + property tests)
# ---------------------------------------------------------------------------

def bin_counts(assignment: Array, n_bins: int) -> Array:
    return jax.ops.segment_sum(jnp.ones_like(assignment, dtype=jnp.int32),
                               assignment, num_segments=n_bins)


def imbalance(assignment: Array, n_bins: int) -> Array:
    """max/mean bin load — 1.0 is perfect balance (the paper's hot-spot metric)."""
    c = bin_counts(assignment, n_bins).astype(jnp.float32)
    return jnp.max(c) / jnp.maximum(jnp.mean(c), 1e-9)


def _kstats():
    """The NeuraScope kernel-stats registry IF it is already imported.

    ``repro.core`` sits below ``repro.sparse`` in the layer order, so it
    must not import it (cycle); recording only when the stats module is
    live in ``sys.modules`` keeps core standalone-importable AND free of
    any import side-effect — the registry simply misses nothing it could
    have seen, because whoever reads stats imported the module first.
    """
    import sys
    return sys.modules.get("repro.sparse.stats")


def bin_balance_snapshot(assignment, n_bins: int) -> dict:
    """Host-side bin-load summary (+ a NeuraScope ``drhm.imbalance`` sample).

    The observability companion to ``imbalance``: the cluster router
    calls it on concrete assignments to leave an auditable balance
    trail per reseed epoch.
    """
    c = np.bincount(np.asarray(assignment, np.int64), minlength=int(n_bins))
    mean = float(c.mean()) if c.size else 0.0
    snap = {"n_bins": int(n_bins), "max": int(c.max(initial=0)),
            "mean": mean,
            "imbalance": float(c.max(initial=0)) / max(mean, 1e-9)}
    st = _kstats()
    if st is not None:
        st.record_value("drhm.imbalance", snap["imbalance"])
        st.record_value("drhm.bin_max", snap["max"])
    return snap


# ---------------------------------------------------------------------------
# Shard planner: DRHM as a distribution policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DRHMShardPlan:
    """Host-side plan assigning ``n_ids`` row ids to ``n_shards`` equally-sized
    shards through the DRHM bijective permutation.

    ``perm[i]``  = position of row i in the hash-shuffled order;
    shard of row i = perm[i] // rows_per_shard.  Because the permutation is a
    bijection, every shard holds exactly ``n_pad / n_shards`` rows, i.e. the
    load balance is *exact*, not just statistical — the pod-scale strengthening
    of the paper's uniformity claim.
    """

    gamma: int
    n_ids: int
    n_pad: int
    n_shards: int
    perm: np.ndarray      # (n_pad,) destination slot of each (padded) row id
    inv_perm: np.ndarray  # (n_pad,) row id occupying each slot

    @property
    def rows_per_shard(self) -> int:
        return self.n_pad // self.n_shards

    def owner_of(self, ids: np.ndarray) -> np.ndarray:
        return self.perm[ids] // self.rows_per_shard

    def slot_of(self, ids: np.ndarray) -> np.ndarray:
        """Slot within the owning shard."""
        return self.perm[ids] % self.rows_per_shard


def plan_row_sharding(n_ids: int, n_shards: int, gamma: int) -> DRHMShardPlan:
    n_pad = ((max(n_ids, n_shards) + n_shards - 1) // n_shards) * n_shards
    g = gamma | 1
    import math
    if math.gcd(n_pad, g) != 1:
        g = coprime_gamma(n_pad, seed=gamma % 5)
    perm = drhm_permutation(n_pad, g)
    st = _kstats()
    if st is not None:
        st.record_count("drhm.shard_plans")
        st.record_value("drhm.shard_n_pad", n_pad)
    return DRHMShardPlan(gamma=g, n_ids=n_ids, n_pad=n_pad,
                         n_shards=n_shards, perm=perm,
                         inv_perm=invert_permutation(perm))


# ---------------------------------------------------------------------------
# Request routing: DRHM one level up (traffic instead of partial products)
# ---------------------------------------------------------------------------

_SM_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_SM_M1 = np.uint64(0xBF58476D1CE4E5B9)
_SM_M2 = np.uint64(0x94D049BB133111EB)


def mix64(z) -> np.ndarray:
    """splitmix64 finalizer (host numpy, wrapping) — the full-width cousin of
    the multiplicative DRHM hash; same stream the serving sampler draws from
    (``sparse.sampler._mix64``).  Used to pre-condition request TAGs before
    the γ-seeded bin permutation, so adversarial seed *values* cannot choose
    their bin by construction — only by searching the (reseedable) map."""
    z = np.asarray(z, np.uint64)
    with np.errstate(over="ignore"):
        z = z + _SM_GAMMA
        z = (z ^ (z >> np.uint64(30))) * _SM_M1
        z = (z ^ (z >> np.uint64(27))) * _SM_M2
        return z ^ (z >> np.uint64(31))


def route_gamma(seed: int, epoch: int) -> int:
    """The reseed sequence for request routing: γ_k = odd(mix64(seed, k)).

    Odd ⇒ coprime to any power-of-two bin count ⇒ every epoch's bin→lane map
    stays an exact-balance bijection (the property the router tests pin)."""
    g = int(mix64(np.uint64(int(seed) % (1 << 32)) * np.uint64(0x51ED2701)
                  ^ np.uint64(int(epoch))))
    return (g & 0xFFFFFFFF) | 1


def plan_request_routing(n_bins: int, n_lanes: int, seed: int = 0,
                         epoch: int = 0) -> DRHMShardPlan:
    """Bin→lane ownership for request routing: the same DRHM bijective
    permutation used for row sharding, applied to a padded power-of-two bin
    space.  Each lane owns exactly ``n_bins / n_lanes`` bins (exact balance
    over bins); *reseeding* (a new epoch ⇒ new γ) re-permutes which bins a
    lane owns, so a seed stream that piles onto one lane under γ_k spreads
    under γ_{k+1} — the paper's dynamic reseeding applied to traffic."""
    st = _kstats()
    if st is not None:
        st.record_count("drhm.route_plans")
        if epoch:
            st.record_count("drhm.route_reseeds")
    return plan_row_sharding(n_bins, n_lanes, route_gamma(seed, epoch))
