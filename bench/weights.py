"""Weights of every tenant, made on the device from the seed in one jitted
call, in float32 (the deployed form: BN already folded, as the FPGA
deployment step leaves them). The program receives them as numpy arrays;
the reference reads the same arrays. He-normal weights, small normal
biases."""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .work import layers


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, n: int, shapes: Tuple[Tuple[int, int, int], ...]):
    out = []
    for i, (c_in, c_out, k) in enumerate(shapes):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(kw, (n, c_out, c_in, k), jnp.float32)
        out.append((w * jnp.sqrt(2.0 / (c_in * k)),
                    0.1 * jax.random.normal(kb, (n, c_out), jnp.float32)))
    return out


def tenant_weights(topo, seed: int, n: int
                   ) -> List[Tuple[Tuple[np.ndarray, np.ndarray], ...]]:
    """n weight sets, ((w (C_out, C_in, K), b (C_out,)), ...) each."""
    shapes = tuple((c_in, c_out, k) for c_in, c_out, k, _ in layers(topo))
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    drawn = [(np.asarray(w), np.asarray(b))
             for w, b in jax.device_get(_draw(key, n, shapes))]
    return [tuple((w[t], b[t]) for w, b in drawn) for t in range(n)]
