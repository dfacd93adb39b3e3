"""Weights from ``--seed`` without a program that depends on it.

The program draws its weights in its startup program from a build seed
that is a constant of that program: a new seed is a new program, which
no compile cache has seen (on the chip a token server's startup took
ten seconds more with every new seed). So every run builds with the
program's default seed and then, in one jitted call on the device with
the seed as an ARGUMENT, flips the sign of each element of each weight
matrix by a fair coin drawn from the seed. The initializers are
symmetric about zero, so the result is a fresh draw from the same
distribution; one-dimensional parameters (biases at 0, layer-norm
scales at 1) stay as they are.
"""
from __future__ import annotations


def reseed(scope, parameters, seed: int) -> int:
    """Re-draw the signs of every 2-D parameter in ``scope`` from
    ``seed``. Returns how many arrays were re-drawn."""
    import jax
    import jax.numpy as jnp
    names = [p.name for p in parameters if len(p.shape or ()) == 2]

    def flip(weights, seed):
        key = jax.random.PRNGKey(seed)
        return [jnp.where(jax.random.bernoulli(
            jax.random.fold_in(key, i), 0.5, w.shape), w, -w)
            for i, w in enumerate(weights)]

    new = jax.jit(flip, donate_argnums=0)(
        [scope.get(n) for n in names], jnp.int32(seed % (2 ** 31 - 1)))
    for n, w in zip(names, new):
        scope.set(n, w)
    return len(names)
