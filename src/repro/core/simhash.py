"""(m, τ)-parameterized SimHash (paper §3.3) + SRHT fast projection.

* ``make_hashes``     — sample the m random-projection hash functions R ∈ R^{m×d}.
* ``hash_codes``      — sign(R x) as {0,1} bits. sign(0) := +1 (deterministic).
* ``pack_signatures`` — merge every τ bits into one of 2^τ bucket ids, giving
  G = m/τ signature groups (paper Eq. 8/11).
* ``collision_expectation`` — E[p̃_j] = (1 − arccos(cos θ)/π)^τ (paper Eq. 13).
* ``srht_hashes`` / ``srht_codes`` — the O(L·m·log d) "Approximating Random
  Projection" the paper cites [Andoni et al. 2015]: a subsampled randomized
  Hadamard transform (H·D sign-flip chain). Used by the §Perf compute
  optimization; plain GEMM SimHash is the paper-faithful baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SimHashConfig:
    m: int = 48          # number of hash functions (paper: 48 online)
    tau: int = 3         # signature width (paper: 3 online)
    d: int = 128         # input dim

    @property
    def n_groups(self) -> int:
        assert self.m % self.tau == 0, (self.m, self.tau)
        return self.m // self.tau

    @property
    def n_buckets(self) -> int:
        return 1 << self.tau


def make_hashes(key: jax.Array, m: int, d: int) -> jax.Array:
    """R ∈ R^{m×d}, rows r_i ~ N(0, I_d)."""
    return jax.random.normal(key, (m, d), dtype=jnp.float32)


def hash_codes(x: jax.Array, R: jax.Array) -> jax.Array:
    """x: (..., d) -> bits (..., m) in {0,1} (int32); bit = [r·x >= 0].
    The projection runs at full fp32 precision: on TPU the default is one
    bf16 pass, whose rounding would flip the sign of near-zero projections
    and bucket behaviors differently from the kernels."""
    proj = jnp.einsum("...d,md->...m", x.astype(jnp.float32), R,
                      precision=jax.lax.Precision.HIGHEST)
    return (proj >= 0).astype(jnp.int32)


def pack_signatures(codes: jax.Array, tau: int) -> jax.Array:
    """codes: (..., m) bits -> bucket ids (..., m/τ) ∈ [0, 2^τ)."""
    *lead, m = codes.shape
    assert m % tau == 0
    grouped = codes.reshape(*lead, m // tau, tau)
    weights = (1 << jnp.arange(tau, dtype=jnp.int32))
    return jnp.sum(grouped * weights, axis=-1)


def signatures(x: jax.Array, R: jax.Array, tau: int) -> jax.Array:
    """Convenience: x (..., d) -> bucket ids (..., G)."""
    return pack_signatures(hash_codes(x, R), tau)


def collision_expectation(cos_sim: jax.Array, tau: int) -> jax.Array:
    """E[p̃] = (1 − arccos(cos θ)/π)^τ. ``cos_sim`` must be a cosine (unit-norm
    dot product); clipped for arccos stability."""
    c = jnp.clip(cos_sim, -1.0, 1.0)
    return (1.0 - jnp.arccos(c) / jnp.pi) ** tau


# ---------------------------------------------------------------------------
# SRHT: subsampled randomized Hadamard transform (fast JL projection)
# ---------------------------------------------------------------------------
def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def fwht(x: jax.Array) -> jax.Array:
    """Fast Walsh–Hadamard transform along the last axis (len must be 2^k).

    log2(d) butterfly stages of reshape + add/sub — O(d log d) and fully
    vectorized (no Python-level data-dependence)."""
    d = x.shape[-1]
    assert d & (d - 1) == 0, f"FWHT needs power-of-2 length, got {d}"
    h = 1
    while h < d:
        x = x.reshape(*x.shape[:-1], d // (2 * h), 2, h)
        a = x[..., 0, :]
        b = x[..., 1, :]
        x = jnp.concatenate([a + b, a - b], axis=-1)
        x = x.reshape(*x.shape[:-2], d)
        h *= 2
    return x


@dataclasses.dataclass(frozen=True)
class SRHTHashes:
    """Structured projection: x -> (H·D2·H·D1 x)[rows] / sqrt(d_pad).

    Two sign-flip + Hadamard rounds give near-Gaussian marginals; ``rows``
    subsamples m coordinates. Equivalent hash family to dense SimHash up to
    small higher-moment deviations (Andoni et al. 2015)."""

    d1: Any        # (d_pad,) ±1
    d2: Any        # (d_pad,) ±1
    rows: Any      # (m,) int32 indices into d_pad
    d: int
    d_pad: int

    def project(self, x: jax.Array) -> jax.Array:
        """x (..., d) -> pre-sign projections (..., m) via two FWHT rounds."""
        pad = self.d_pad - self.d
        xf = x.astype(jnp.float32)
        if pad:
            xf = jnp.concatenate(
                [xf, jnp.zeros((*x.shape[:-1], pad), jnp.float32)], axis=-1
            )
        y = fwht(xf * self.d1)
        y = fwht(y * self.d2)
        return jnp.take(y, self.rows, axis=-1)

    def codes(self, x: jax.Array) -> jax.Array:
        return (self.project(x) >= 0).astype(jnp.int32)

    def dense_matrix(self) -> jax.Array:
        """The (m, d) matrix R with R @ x == project(x) for all x.

        S·H·D2·H·D1 is linear, so applying it to I_d recovers the dense
        equivalent. Lets the SRHT family feed kernels that take a dense
        projection operand (same hash family; the O(m·log d) FWHT chain stays
        the fast host-side formulation)."""
        return self.project(jnp.eye(self.d, dtype=jnp.float32)).T


def srht_hashes(key: jax.Array, m: int, d: int) -> SRHTHashes:
    d_pad = _next_pow2(max(d, m))
    k1, k2, k3 = jax.random.split(key, 3)
    d1 = jax.random.rademacher(k1, (d_pad,), dtype=jnp.float32)
    d2 = jax.random.rademacher(k2, (d_pad,), dtype=jnp.float32)
    rows = jax.random.choice(k3, d_pad, (m,), replace=False).astype(jnp.int32)
    return SRHTHashes(d1=d1, d2=d2, rows=rows, d=d, d_pad=d_pad)


def srht_signatures(x: jax.Array, h: SRHTHashes, tau: int) -> jax.Array:
    return pack_signatures(h.codes(x), tau)
