"""Weights from --seed as a run-time argument (chipbench/weights.py)."""
import numpy as np

from chipbench import weights


class _Param:
    def __init__(self, name, shape):
        self.name, self.shape = name, shape


def _scope(values):
    from paddle_tpu.core.scope import Scope
    sc = Scope()
    for n, v in values.items():
        sc.set(n, v)
    return sc


def test_reseed_redraws_signs_of_matrices_only():
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    w = rng.randn(64, 48).astype(np.float32)
    params = [_Param("w", (64, 48)), _Param("scale", (48,)),
              _Param("w2", (48, 8))]

    def draw(seed):
        sc = _scope({"w": jnp.asarray(w), "scale": jnp.ones(48),
                     "w2": jnp.asarray(w[:48, :8])})
        assert weights.reseed(sc, params, seed) == 2
        return {n: np.asarray(sc.get(n)) for n in ("w", "scale", "w2")}

    a, b, again = draw(1), draw(3_000_000_001), draw(1)
    np.testing.assert_array_equal(a["w"], again["w"])
    np.testing.assert_array_equal(np.abs(a["w"]), np.abs(w))
    np.testing.assert_array_equal(a["scale"], np.ones(48))
    flipped = np.mean(np.sign(a["w"]) != np.sign(w))
    assert 0.4 < flipped < 0.6
    assert 0.4 < np.mean(np.sign(a["w"]) != np.sign(b["w"])) < 0.6
    # the two matrices get coins of their own
    assert not np.array_equal(np.sign(a["w"][:48, :8]) * np.sign(w[:48, :8]),
                              np.sign(a["w2"]) * np.sign(w[:48, :8]))
