"""Seeds: ``--seed`` is any whole number up to a little over 2**31."""
from __future__ import annotations

import numpy as np


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for one named stream of one run."""
    return np.random.default_rng([int(seed), int(stream)])


def device_key(seed: int):
    """A device key of the hardware bit generator (cheap to compile and
    to run for billions of draws); the seed is folded in two halves so
    that one over 31 bits needs no 64-bit mode."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def redraw(params, seed: int, out_shardings=None):
    """Replace every matrix of the pytree ``params`` by a normal draw of
    the same shape, dtype and standard deviation, on the device, in one
    jitted call from ``seed``; vectors (norm gains, biases) stay. The
    old buffers are donated, so a model is never held twice."""
    import jax
    import jax.numpy as jnp

    def draw(tree, key):
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(key, len(leaves))
        out = []
        for a, k in zip(leaves, keys):
            if a.ndim < 2:
                out.append(a)
                continue
            std = jnp.std(a.astype(jnp.float32))
            out.append((std * jax.random.normal(
                k, a.shape, jnp.float32)).astype(a.dtype))
        return treedef.unflatten(out)

    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(draw, donate_argnums=0, **kw)(params,
                                                 device_key(seed))
