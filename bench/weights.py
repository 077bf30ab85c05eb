"""Seeded weights, made on the device in one jitted call.

Every weight is drawn from ``--seed`` by the benchmark itself, rounded to
a bf16 value (how deployments store weights) and held as f32, the type
the program serves in.  The reference reads the same arrays; the program
is handed them and never writes them.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int, stream: str) -> jax.Array:
    """A JAX key from a seed of any size and a stream name."""
    words = np.random.SeedSequence(
        [int(seed), zlib.crc32(stream.encode())]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _draw(key, shape, how):
    kind, scale = how
    if kind == "normal":
        return scale * jax.random.normal(key, shape, jnp.float32)
    if kind == "one_plus_normal":
        return 1.0 + scale * jax.random.normal(key, shape, jnp.float32)
    if kind == "log_uniform_a":      # A_log = log A, A ~ U[1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":    # softplus^-1(dt), log dt ~ U[ln 1e-3, ln 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown draw {kind!r}")


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, value in flat.items():
        *parents, leaf = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return out


def make(spec: dict, seed: int) -> dict:
    """The weight tree of ``spec`` (``path -> (shape, draw)``) for
    ``seed``, as bf16 values in f32 arrays on the default device."""
    names = sorted(spec)

    def gen(key):
        keys = jax.random.split(key, len(names))
        return {n: _draw(k, spec[n][0], spec[n][1])
                .astype(jnp.bfloat16).astype(jnp.float32)
                for n, k in zip(names, keys)}

    flat = jax.jit(gen)(key_for(seed, "weights"))
    return _nest(flat)
