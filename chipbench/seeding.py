"""Seeds: ``--seed`` is any whole number up to a little over 2**31."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for one named stream of one run."""
    return np.random.default_rng([int(seed), int(stream)])


def device_key(seed: int):
    """A device key of the hardware bit generator (cheap to compile and
    to run for billions of draws); the seed is folded in two halves so
    that one over 31 bits needs no 64-bit mode."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def _draw(a, key):
    std = jnp.std(a.astype(jnp.float32))
    return (std * jax.random.normal(key, a.shape, jnp.float32)
            ).astype(a.dtype)


@functools.lru_cache(maxsize=None)
def _drawer(sharding):
    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(_draw, donate_argnums=0, **kw)


def redraw(params, seed: int, out_shardings=None):
    """Replace every matrix of the pytree ``params`` by a normal draw of
    the same shape, dtype and standard deviation, on the device, from
    ``seed``; vectors (norm gains, biases) stay. One small jitted
    program a distinct shape, called leaf after leaf without a wait
    between them (one program over all leaves compiled for half a minute
    and took 14 MB of the compile cache); each old buffer is donated, so
    a model is never held twice."""
    leaves, treedef = jax.tree.flatten(params)
    shardings = [None] * len(leaves) if out_shardings is None \
        else treedef.flatten_up_to(out_shardings)
    keys = jax.random.split(device_key(seed), len(leaves))
    out = []
    for a, k, sh in zip(leaves, keys, shardings):
        if a.ndim < 2:
            out.append(a)
            continue
        out.append(_drawer(sh)(a, k))
    return treedef.unflatten(out)
