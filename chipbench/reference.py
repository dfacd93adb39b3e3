"""Plain references for the benchmark's configurations: straightforward
``jax.numpy`` in float32 under ``default_matmul_precision("highest")``,
no kernels, no cache, no batching tricks. Weights are the system's own,
read from its scope as a "tape": the parameter arrays in the order the
program created them (``main.all_parameters()``), consumed here in the
same order the model code makes them.

Both follow "Attention Is All You Need" (arXiv:1706.03762) base as
models/transformer.py builds it. Departures from the paper, all the
program's own and mirrored here so that the two can agree: embeddings
are not scaled by sqrt(d_model); input and output embeddings are not
tied; dropout is 0; the decoder LM drops cross-attention.
"""
from __future__ import annotations

import numpy as np

NEG = -1e9
LN_EPS = 1e-5


def position_table(max_len: int, d_model: int) -> np.ndarray:
    """Sinusoid positions, section 3.5 of the paper: sin on even
    dimensions, cos on odd ones, wavelength 10000^(2i/d)."""
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    i = np.arange(d_model, dtype=np.float64)[None, :]
    angle = pos / np.power(10000.0, 2.0 * np.floor(i / 2.0) / d_model)
    table = np.where(np.arange(d_model)[None, :] % 2 == 0,
                     np.sin(angle), np.cos(angle))
    return table.astype(np.float32)


class _Tape:
    def __init__(self, arrays):
        self._it = iter(arrays)

    def take(self, n=1):
        out = [next(self._it) for _ in range(n)]
        return out[0] if n == 1 else out

    def done(self) -> bool:
        return next(self._it, None) is None


def _layer_norm(x, scale, bias):
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def _attention(xq, xkv, wq, wk, wv, wo, n_head, bias):
    """Multi-head attention, section 3.2. xq [b, Sq, d], xkv [b, Sk, d],
    bias additive, broadcastable to [b, h, Sq, Sk]."""
    import jax
    import jax.numpy as jnp
    b, sq, d = xq.shape
    dh = d // n_head

    def heads(x, w):
        return (x @ w).reshape(b, -1, n_head, dh).transpose(0, 2, 1, 3)

    q, k, v = heads(xq, wq), heads(xkv, wk), heads(xkv, wv)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(dh) + bias
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, sq, d) @ wo


def _ffn(x, w1, b1, w2, b2):
    import jax.numpy as jnp
    return jnp.maximum(x @ w1 + b1, 0.0) @ w2 + b2


def _encdec_sums(tape_arrays, src, trg, labels, n_layer, n_head,
                 pad_id=0):
    """(summed token loss over non-pad labels, their count) for integer
    [b, S] arrays."""
    import jax
    import jax.numpy as jnp
    t = _Tape(tape_arrays)
    seq = src.shape[1]
    d_model = tape_arrays[0].shape[1]
    pe = jnp.asarray(position_table(seq, d_model))
    src_bias = jnp.where(src == pad_id, NEG, 0.0)[:, None, None, :]
    trg_bias = jnp.where(trg == pad_id, NEG, 0.0)[:, None, None, :]
    causal = jnp.triu(jnp.full((seq, seq), NEG, jnp.float32), k=1)

    x = t.take()[src] + pe
    for _ in range(n_layer):
        wq, wk, wv, wo = t.take(4)
        x = _layer_norm(x + _attention(x, x, wq, wk, wv, wo, n_head,
                                       src_bias), *t.take(2))
        x = _layer_norm(x + _ffn(x, *t.take(4)), *t.take(2))
    enc = x
    y = t.take()[trg] + pe
    for _ in range(n_layer):
        wq, wk, wv, wo = t.take(4)
        y = _layer_norm(y + _attention(y, y, wq, wk, wv, wo, n_head,
                                       trg_bias + causal), *t.take(2))
        wq, wk, wv, wo = t.take(4)
        y = _layer_norm(y + _attention(y, enc, wq, wk, wv, wo, n_head,
                                       src_bias), *t.take(2))
        y = _layer_norm(y + _ffn(y, *t.take(4)), *t.take(2))
    w, b = t.take(2)
    assert t.done(), "the parameter tape is longer than the model"
    logp = jax.nn.log_softmax(y @ w + b, axis=-1)
    tok = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    keep = (labels != pad_id).astype(jnp.float32)
    return jnp.sum(tok * keep), jnp.sum(keep)


def _encdec_batch(batch: dict):
    return tuple(np.asarray(batch[k]).reshape(
        batch[k].shape[0], -1).astype(np.int32)
        for k in ("src_ids", "trg_ids", "trg_labels"))


def encdec_loss(tape_arrays, batch: dict, model: dict,
                chunk_tokens: int = 4096) -> float:
    """Mean token loss of the encoder-decoder transformer on one feed
    batch ({"src_ids", "trg_ids", "trg_labels"}: [b, S, 1] ids), a few
    sequences at a time so that the [rows, S, vocab] logits fit.
    ``model`` is the configuration's builder arguments."""
    import jax
    import jax.numpy as jnp
    src, trg, lbl = _encdec_batch(batch)
    rows = max(1, chunk_tokens // src.shape[1])
    tape = [jnp.asarray(a, jnp.float32) for a in tape_arrays]
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(_encdec_sums, static_argnums=(4, 5))
        total = count = 0.0
        for i in range(0, src.shape[0], rows):
            s, c = fn(tape, src[i:i + rows], trg[i:i + rows],
                      lbl[i:i + rows], int(model["n_layer"]),
                      int(model["n_head"]))
            total += float(s)
            count += float(c)
    return total / max(count, 1.0)


def encdec_grads(tape_arrays, batch: dict, model: dict,
                 chunk_tokens: int = 4096) -> list:
    """Gradient of that mean token loss with respect to every array of
    the tape (``jax.grad`` of the same plain forward, f32 "highest"),
    accumulated a few sequences at a time. Device arrays, tape order."""
    import jax
    import jax.numpy as jnp
    src, trg, lbl = _encdec_batch(batch)
    rows = max(1, chunk_tokens // src.shape[1])
    tape = [jnp.asarray(a, jnp.float32) for a in tape_arrays]

    def summed(tape, s, t, y, n_layer, n_head):
        return _encdec_sums(tape, s, t, y, n_layer, n_head)[0]

    count = float(np.sum(lbl != 0))
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(jax.grad(summed), static_argnums=(4, 5))
        acc = jax.jit(lambda a, b: [x + y for x, y in zip(a, b)],
                      donate_argnums=0)
        total = None
        for i in range(0, src.shape[0], rows):
            g = fn(tape, src[i:i + rows], trg[i:i + rows],
                   lbl[i:i + rows], int(model["n_layer"]),
                   int(model["n_head"]))
            total = g if total is None else acc(total, g)
    return [g / max(count, 1.0) for g in total]


def adam_first_step(grads, lr: float, beta1=0.9, beta2=0.999,
                    eps=1e-8) -> list:
    """What Adam (Kingma & Ba, Algorithm 1, with the bias correction
    folded into the rate as the program's op does) adds to each weight
    in its FIRST step, from zero moments: m = (1-b1) g, v = (1-b2) g^2,
    rate lr sqrt(1-b2)/(1-b1), so -lr g / (|g| + eps / sqrt(1-b2))."""
    import jax.numpy as jnp
    del beta1                       # cancels in the first step
    floor = eps / np.sqrt(1.0 - beta2)
    return [-lr * g / (jnp.abs(g) + floor) for g in grads]


def descent_share(grads, applied, wanted) -> dict:
    """The first-order decrease of the loss that the applied update
    buys (-sum g * applied) as a share of what the reference update
    buys (-sum g * wanted): 1 for the reference's own step, about 0 for
    an update that has nothing to do with the gradient, negative for
    one that climbs. ``per_array`` has None where the reference
    gradient is all zero; ``overall`` is over all arrays together."""
    import jax.numpy as jnp
    got = [float(-jnp.sum(g * a)) for g, a in zip(grads, applied)]
    want = [float(-jnp.sum(g * w)) for g, w in zip(grads, wanted)]
    return {"per_array": [a / w if w > 0 else None
                          for a, w in zip(got, want)],
            "overall": sum(got) / sum(want) if sum(want) > 0 else None}


def _lm_logits(tape_arrays, tokens, n_layer, n_head):
    import jax.numpy as jnp
    t = _Tape(tape_arrays)
    seq = tokens.shape[1]
    d_model = tape_arrays[0].shape[1]
    pe = jnp.asarray(position_table(seq, d_model))
    causal = jnp.triu(jnp.full((seq, seq), NEG, jnp.float32), k=1)
    x = t.take()[tokens] + pe
    for _ in range(n_layer):
        wq, wk, wv, wo = t.take(4)
        x = _layer_norm(x + _attention(x, x, wq, wk, wv, wo, n_head,
                                       causal), *t.take(2))
        x = _layer_norm(x + _ffn(x, *t.take(4)), *t.take(2))
    w, b = t.take(2)
    assert t.done(), "the parameter tape is longer than the model"
    return x @ w + b


def _lm_choice_gap(tape_arrays, tokens, n_layer, n_head):
    import jax.numpy as jnp
    logits = _lm_logits(tape_arrays, tokens, n_layer, n_head)[:, :-1]
    chosen = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.max(logits, axis=-1) - chosen


def lm_choice_gap(tape_arrays, tokens: np.ndarray, n_layer: int,
                  n_head: int) -> np.ndarray:
    """[b, S-1]: how far the reference's logit of the token that
    actually follows position t lies below its best logit at t (0 where
    the next token is the reference's argmax). Reduced on the device, so
    the [b, S, vocab] logits never cross to the host."""
    import jax
    import jax.numpy as jnp
    tape = [jnp.asarray(a, jnp.float32) for a in tape_arrays]
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(_lm_choice_gap, static_argnums=(2, 3))
        return np.asarray(fn(tape, np.asarray(tokens, np.int32),
                             n_layer, n_head))
