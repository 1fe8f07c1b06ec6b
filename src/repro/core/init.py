"""Centroid initialization: random subset and k-means++ (exact D² sampling)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def random_indices(key: Array, n: int, k: int) -> Array:
    """The row indices of ``k`` distinct points out of ``n``, uniformly
    sampled: what ``random_init`` takes, and what the sharded init
    (``ParallelContext.make_random_init``) takes shard by shard."""
    if k > n:
        raise ValueError(
            f"random_init needs at least k data points to draw k distinct "
            f"centroids, got k={k} > n={n}")
    return jax.random.choice(key, n, (k,), replace=False)


def random_init(key: Array, x: Array, k: int) -> Array:
    """k distinct data points, uniformly sampled."""
    return jnp.take(x, random_indices(key, x.shape[0], k), axis=0)


def owned_rows(x_local: Array, idx: Array, lo) -> Array:
    """Rows ``idx`` of a global array of which ``x_local`` holds rows
    ``lo, lo + 1, ...``: the rows it holds, zeros in place of the rest.
    Summed over the shards (a psum), these are exactly the rows."""
    rel = idx - lo
    own = (rel >= 0) & (rel < x_local.shape[0])
    rows = jnp.take(x_local, jnp.clip(rel, 0, x_local.shape[0] - 1), axis=0)
    return jnp.where(own[:, None], rows, jnp.zeros_like(rows))


def kmeans_plus_plus(key: Array, x: Array, k: int) -> Array:
    """Exact k-means++ (Arthur & Vassilvitskii): each next centroid is drawn
    with probability proportional to its squared distance to the closest
    already-chosen centroid. O(NKd) total, fully jittable."""
    n, d = x.shape
    x32 = x.astype(jnp.float32)
    xsq = jnp.sum(x32 * x32, axis=-1)

    k0, key = jax.random.split(key)
    first = jnp.take(x32, jax.random.randint(k0, (), 0, n), axis=0)

    def dist_to(c):
        return jnp.maximum(
            xsq + jnp.sum(c * c) - 2.0 * (x32 @ c), 0.0)

    def body(i, carry):
        cents, min_d, key = carry
        key, kd = jax.random.split(key)
        # Gumbel-max categorical draw proportional to min_d. When every
        # remaining min_d is zero (all points coincide with a chosen
        # centroid) the D² distribution is degenerate; fall back to a
        # uniform draw instead of argmax-over-(-inf) always picking row 0.
        logits = jnp.where(min_d > 0, jnp.log(min_d), -jnp.inf)
        logits = jnp.where(jnp.any(min_d > 0), logits,
                           jnp.zeros_like(logits))
        idx = jnp.argmax(logits + jax.random.gumbel(kd, (n,)))
        c_new = jnp.take(x32, idx, axis=0)
        cents = jax.lax.dynamic_update_index_in_dim(cents, c_new, i, 0)
        min_d = jnp.minimum(min_d, dist_to(c_new))
        return cents, min_d, key

    cents = jnp.zeros((k, d), jnp.float32)
    cents = jax.lax.dynamic_update_index_in_dim(cents, first, 0, 0)
    min_d = dist_to(first)
    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, min_d, key))
    return cents.astype(x.dtype)


def init_centroids(key: Array, x: Array, k: int, method: str) -> Array:
    if method == "random":
        return random_init(key, x, k)
    if method in ("kmeans++", "k-means++", "plusplus"):
        return kmeans_plus_plus(key, x, k)
    raise ValueError(f"unknown init method {method!r}")
