"""The plain references' own arithmetic (chipbench/reference.py): the
chunked gradient, Adam's first step, and the score that holds a
training step's update to them."""
import numpy as np
import pytest

from chipbench import reference

D, DI, V, H, S = 16, 32, 40, 2, 8


def _tape(rng):
    def attn():
        return [rng.normal(0, 0.2, (D, D)) for _ in range(4)]

    def ln():
        return [np.ones(D), np.zeros(D)]

    def ffn():
        return [rng.normal(0, 0.2, (D, DI)), np.zeros(DI),
                rng.normal(0, 0.2, (DI, D)), np.zeros(D)]

    enc = attn() + ln() + ffn() + ln()
    dec = attn() + ln() + attn() + ln() + ffn() + ln()
    tape = [rng.normal(0, 0.2, (V, D))] + enc + \
        [rng.normal(0, 0.2, (V, D))] + dec + \
        [rng.normal(0, 0.2, (D, V)), np.zeros(V)]
    return [np.asarray(a, np.float32) for a in tape]


def _batch(rng, rows):
    return {k: rng.integers(1, V, (rows, S, 1)).astype(np.int64)
            for k in ("src_ids", "trg_ids", "trg_labels")}


MODEL = {"n_layer": 1, "n_head": H}


def test_gradient_in_chunks_is_the_gradient_and_descends():
    rng = np.random.default_rng(0)
    tape, batch = _tape(rng), _batch(rng, 4)
    whole = reference.encdec_grads(tape, batch, MODEL, chunk_tokens=10**6)
    chunks = reference.encdec_grads(tape, batch, MODEL, chunk_tokens=S)
    assert len(whole) == len(tape)
    for a, b in zip(whole, chunks):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-7)
    # a small step against it lowers the loss by |g|^2 * step
    before = reference.encdec_loss(tape, batch, MODEL, chunk_tokens=S)
    step = 1e-3
    moved = [w - step * np.asarray(g) for w, g in zip(tape, whole)]
    after = reference.encdec_loss(moved, batch, MODEL)
    norm2 = sum(float(np.sum(np.square(np.asarray(g)))) for g in whole)
    assert after < before
    assert abs((before - after) / (step * norm2) - 1.0) < 0.05


def test_adam_first_step_is_a_rate_sized_step_against_the_sign():
    g = [np.array([1e-3, -2.0, 0.0, 1e-12], np.float32)]
    (u,) = reference.adam_first_step(g, lr=1e-3)
    u = np.asarray(u)
    np.testing.assert_allclose(u[:2], [-1e-3, 1e-3], rtol=1e-3)
    assert u[2] == 0.0 and abs(u[3]) < 1e-8          # eps rules there
    # the algorithm itself, step 1, bias-corrected
    b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-3
    m, v = (1 - b1) * g[0], (1 - b2) * g[0] ** 2
    want = -lr * np.sqrt(1 - b2) / (1 - b1) * m / (np.sqrt(v) + eps)
    np.testing.assert_allclose(u, want, rtol=1e-5, atol=1e-12)


@pytest.mark.parametrize("applied, low, high", [
    ("wanted", 0.999, 1.001), ("noise", -0.2, 0.2), ("climb", -1.001,
                                                     -0.999)])
def test_descent_share_tells_a_right_update_from_a_wrong_one(
        applied, low, high):
    rng = np.random.default_rng(1)
    grads = [rng.normal(0, 1e-3, (64, 32)).astype(np.float32),
             np.zeros((8,), np.float32)]
    wanted = [np.asarray(w) for w in
              reference.adam_first_step(grads, lr=1e-3)]
    update = {"wanted": wanted,
              "noise": [1e-3 * np.sign(rng.normal(size=w.shape))
                        .astype(np.float32) for w in wanted],
              "climb": [-w for w in wanted]}[applied]
    share = reference.descent_share(grads, update, wanted)
    assert low <= share["overall"] <= high
    assert low <= share["per_array"][0] <= high
    assert share["per_array"][1] is None     # no gradient, no verdict
