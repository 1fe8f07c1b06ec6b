"""Seeded data for the benchmark, made on the device.

``blobs`` draws points around Gaussian centres (centre scale 5, noise
0.4) in fixed-size chunks, so no full-size temporary exists besides the
output. The same key gives the same points on every run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """The run's root key; any whole number, also beyond 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              (seed >> 31) % (1 << 31))


def _chunk(n: int, chunk: int) -> int:
    """The largest divisor of ``n`` not above ``chunk``."""
    chunk = min(chunk, n)
    while n % chunk:
        chunk -= 1
    return chunk


def blob_centers(key, d: int, n_centers: int):
    """The centres ``blobs`` draws around, for a given key."""
    return jax.random.normal(jax.random.split(key)[0], (n_centers, d)) * 5.0


def blobs(key, n: int, d: int, n_centers: int, chunk: int = 1 << 18):
    """``(n, d)`` float32 points around ``n_centers`` centres."""
    chunk = _chunk(n, chunk)

    @jax.jit
    def gen(key):
        kc, kp = jax.random.split(key)
        centers = jax.random.normal(kc, (n_centers, d)) * 5.0

        def one(i):
            ka, kn = jax.random.split(jax.random.fold_in(kp, i))
            lbl = jax.random.randint(ka, (chunk,), 0, n_centers)
            return centers[lbl] + 0.4 * jax.random.normal(kn, (chunk, d))

        return jax.lax.map(one, jnp.arange(n // chunk)).reshape(n, d)

    x = gen(key)
    x.block_until_ready()
    return x


def held_out(key, n: int, d: int, n_centers: int, corpus_key):
    """``n`` fresh draws from the distribution of ``blobs(corpus_key, ...)``:
    the same centres, new labels and noise, so no draw is a corpus row."""
    centers = blob_centers(corpus_key, d, n_centers)

    @jax.jit
    def gen(key, centers):
        ka, kn = jax.random.split(key)
        lbl = jax.random.randint(ka, (n,), 0, n_centers)
        return centers[lbl] + 0.4 * jax.random.normal(kn, (n, d))

    return gen(key, centers)
