"""Plain reference of the ``zaya1-8b`` configuration: the forward pass of
the published block stack as chipbench/configs/zaya1-8b.json states it,
in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, with nothing imported from
the program under test.

It is written the slow, obvious way on purpose: no cache, no carried
window, no sorting of rows by expert. With d = ``hidden_size``, d_h =
``head_dim``, h query heads over c key heads (group g = h / c), every
layer is ``x += CCA(RMS(x; w_a))`` then ``x += MoE(RMS(x; w_m))`` and
the logits are ``E^T RMS(x; w_f)`` through the tied embedding.

*CCA* of the normed input u [S, d]: ``q~ = u W_q`` [S, h d_h], ``k~ = u
W_k`` [S, c d_h]; ``v_t = [u_t W_v1 ; u_(t-1) W_v2]`` (``u_(-1) = 0``).
With ``z = [q~ ; k~]`` as h + c heads of d_h: ``a_t = c0[0] z_(t-1) +
c0[1] z_t + b0`` (depthwise), ``b_t[j] = a_(t-1)[j] C1[0, j] + a_t[j]
C1[1, j] + b1[j]`` (a d_h x d_h matrix a head a tap; z and a are zero
before the first token). Then ``q_t[i] = b_t^q[i] + (q~_t[i] +
k~_t[c(i)]) / 2``, ``k_t[j] = b_t^k[j] + (mean over j's g query heads of
q~_t[i] + k~_t[j]) / 2``, each head scaled to length sqrt(d_h) (a key
head times its tau), the first ``partial_rotary_factor`` d_h columns of
every head turned by the rotary embedding (rotate-half, position t),
softmax attention over s <= t with scores over sqrt(d_h), the heads'
outputs through W_o.

*MoE* of the normed input u: ``r_l = u W_d + gamma_l r_(l-1)`` (no
carried term in the first layer), ``s = gelu(gelu(r W_1 + b_1) W_2 +
b_2) W_3`` (erf GELU), ``p = softmax(s)``, ``e* = argmax(p + beta)``,
``MoE = p_(e*) (silu(u W_gate,e*) * (u W_up,e*)) W_down,e*`` — EVERY
expert is applied to every row and a mask keeps the pick's.

The weights are a TAPE: the arrays in the order the program created its
parameters (``LAYER_ARRAYS``), at whatever width they are stored —
widened to float32 here, which is exact. They are taken from the host a
layer at a time, and every batch of rows passes through a layer before
the next is uploaded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# a layer's arrays on the tape, in order; the first layer has no gamma
LAYER_ARRAYS = ("norm_a", "c0", "b0", "c1", "b1", "tau", "w_q", "w_k",
                "w_v1", "w_v2", "w_o", "norm_m", "w_d", "w_1", "b_1",
                "w_2", "b_2", "w_3", "gamma", "beta", "gate", "up", "down")
# positions a call of the head holds at once, and vocabulary rows:
# [positions, rows] float32 logits beside the rows widened to float32
HEAD_CHUNK = 2048
VOCAB_CHUNK = 65536


def layers_of(tape: list, n_layer: int) -> tuple:
    """(embedding, [dict of one layer's arrays], final norm scale)."""
    at, out = 1, []
    for i in range(n_layer):
        names = [n for n in LAYER_ARRAYS if i or n != "gamma"]
        out.append(dict(zip(names, tape[at:at + len(names)])))
        at += len(names)
    if at + 1 != len(tape):
        raise ValueError(f"the tape holds {len(tape)} arrays; the stack "
                         f"reads {at + 1}")
    return tape[0], out, tape[at]


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _shifted(t):
    """t [rows, S, ..] one position later, zeros first."""
    return jnp.pad(t, ((0, 0), (1, 0)) + ((0, 0),) * (t.ndim - 2))[:, :-1]


def _rotary(t, theta, r):
    """t [rows, heads, S, d_h]: the first r columns of every head turned
    at the row's position, columns (i, i + r/2) a pair."""
    s = t.shape[2]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = t[..., :r // 2], t[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            t[..., r:]], -1)


def cca(u, w, arch):
    """u [rows, S, d] -> (the sublayer's output, what a server keeps of
    these rows: keys and values as attention reads them [rows, c, S,
    d_h], and z, a, u W_v2 at every position)."""
    h, c, dh = arch["heads"], arch["kv_heads"], arch["head_dim"]
    rows, s, _ = u.shape
    g = h // c
    q0, k0 = u @ w["w_q"], u @ w["w_k"]
    v2 = u @ w["w_v2"]
    v = jnp.concatenate([u @ w["w_v1"], _shifted(v2)], -1)
    z = jnp.concatenate([q0, k0], -1)
    a = w["c0"][0] * _shifted(z) + w["c0"][1] * z + w["b0"]
    c1 = w["c1"].reshape(2, h + c, dh, dh)

    def by_head(t, m):           # [rows, S, (h + c) d_h] x [h + c, d_h, d_h]
        return jnp.einsum("rshi,hio->rsho",
                          t.reshape(rows, s, h + c, dh), m).reshape(t.shape)

    b = by_head(_shifted(a), c1[0]) + by_head(a, c1[1]) + w["b1"]
    q0 = q0.reshape(rows, s, c, g, dh)
    k0 = k0.reshape(rows, s, c, 1, dh)
    q = b[..., :h * dh].reshape(rows, s, c, g, dh) + (q0 + k0) / 2
    k = b[..., h * dh:].reshape(rows, s, c, 1, dh) \
        + (jnp.mean(q0, 3, keepdims=True) + k0) / 2

    def unit(t):
        return t * jnp.sqrt(dh) / jnp.sqrt(
            jnp.sum(t * t, -1, keepdims=True) + arch["norm_floor"])

    q = unit(q).reshape(rows, s, h, dh).transpose(0, 2, 1, 3)
    k = (unit(k) * w["tau"][:, None, None]).reshape(rows, s, c, dh) \
        .transpose(0, 2, 1, 3)
    r = int(dh * arch["partial_rotary_factor"])
    q, keys = _rotary(q, arch["rope_theta"], r), \
        _rotary(k, arch["rope_theta"], r)
    values = v.reshape(rows, s, c, dh).transpose(0, 2, 1, 3)
    seen = jnp.tril(jnp.ones((s, s), bool))

    def one(qkv):                      # a row at a time: [h, S, S] scores
        q1, k1, v1 = qkv
        scores = jnp.einsum("hqd,hkd->hqk", q1, jnp.repeat(k1, g, 0)) \
            / jnp.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", probs, jnp.repeat(v1, g, 0))

    o = jax.lax.map(one, (q, keys, values))
    out = o.transpose(0, 2, 1, 3).reshape(rows, s, h * dh) @ w["w_o"]
    return out, (keys, values, z, a, v2)


def moe(u, w, r_prev, arch, forced=None):
    """u [rows, S, d] -> (the sublayer's output, r, each row's pick
    [rows, S], its margin p_1 - p_2 and the pick's deficit: how far its
    selection score lies under the best). ``forced`` [rows, S] takes
    the place of the pick (an id outside the experts: no expert)."""
    d, f = u.shape[-1], arch["expert_width"]
    r = u @ w["w_d"]
    if r_prev is not None:
        r = r + w["gamma"][0] * r_prev
    hid = jax.nn.gelu(r @ w["w_1"] + w["b_1"], approximate=False)
    hid = jax.nn.gelu(hid @ w["w_2"] + w["b_2"], approximate=False)
    p = jax.nn.softmax(hid @ w["w_3"], -1)
    pick = jnp.argmax(p + w["beta"], -1) if forced is None else forced
    best = jnp.sort(p + w["beta"], -1)
    at = jnp.clip(pick, 0, p.shape[-1] - 1)[..., None]
    weight = jnp.take_along_axis(p, at, -1)
    deficit = best[..., -1] - jnp.take_along_axis(p + w["beta"], at,
                                                  -1)[..., 0]

    gates, ups = w["gate"].reshape(-1, d, f), w["up"].reshape(-1, d, f)
    downs = w["down"].reshape(-1, f, d)

    def add_expert(total, e):
        g = u @ gates[e]
        y = (g * jax.nn.sigmoid(g) * (u @ ups[e])) @ downs[e]
        return total + jnp.where((pick == e)[..., None], weight * y, 0.0), \
            None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u),
                          jnp.arange(arch["experts"]))
    return out, r, pick, best[..., -1] - best[..., -2], deficit


@functools.partial(jax.jit, static_argnames=("arch", "with_state"))
def _layer(x, r_prev, w, arch, with_state=False, forced=None):
    """One layer: (x, r, pick, margin, deficit) and, ``with_state``,
    what ``cca`` keeps."""
    arch = dict(arch)
    with jax.default_matmul_precision("highest"):
        w = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a).astype(jnp.float32), w)
        mixed, kept = cca(_rms(x, w["norm_a"], arch["eps"]), w, arch)
        x = x + mixed
        routed, r, pick, margin, deficit = moe(
            _rms(x, w["norm_m"], arch["eps"]), w, r_prev, arch, forced)
        out = (x + routed, r, pick, margin, deficit)
        return out + (kept,) if with_state else out


def _static(arch) -> tuple:
    """The numbers a layer reads, hashable for jit, from the published
    keys."""
    rope = arch["rope_parameters"]["hybrid"]
    return tuple(dict(
        heads=arch["num_attention_heads"],
        kv_heads=arch["num_key_value_heads"], head_dim=arch["head_dim"],
        partial_rotary_factor=float(rope["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        experts=arch["num_experts"],
        expert_width=arch["moe_intermediate_size"],
        eps=float(arch["rms_norm_eps"]), norm_floor=1e-12).items())


def forward(tape: list, batches: list, arch: dict,
            with_state: bool = False, picks=None):
    """For each int token array [rows, S] of ``batches``: the stack's
    output before the last norm [rows, S, d] float32 and, a layer, the
    picks [rows, S], their margins and deficits; ``with_state`` adds
    what every layer's ``cca`` keeps. ``picks`` (a [layers, rows, S]
    array a batch) FORCES the routing: the pass then follows another
    system's choices, and the deficits say what each cost. Returns (xs,
    routing, kept): routing[b][l] = (pick, margin, deficit), kept[b][l]
    the state."""
    n_layer = len(arch["layer_types"])
    table, layers, _ = layers_of(tape, n_layer)
    static = _static(arch)
    table = jnp.asarray(table)
    xs = [jnp.take(table, jnp.asarray(t, jnp.int32), axis=0)
          .astype(jnp.float32) for t in batches]
    rs = [None] * len(batches)
    routing = [[] for _ in batches]
    kept = [[] for _ in batches]
    for i, w in enumerate(layers):
        w = {k: jnp.asarray(v) for k, v in w.items()}     # one upload
        outs = [_layer(x, r, w, static, with_state,
                       None if picks is None
                       else jnp.asarray(picks[b][i], jnp.int32))
                for b, (x, r) in enumerate(zip(xs, rs))]
        # the next layer's upload waits for this layer's work
        jax.block_until_ready(outs)
        xs = [o[0] for o in outs]
        rs = [o[1] for o in outs]
        for b, o in enumerate(outs):
            routing[b].append(tuple(np.asarray(t) for t in o[2:5]))
            if with_state:
                kept[b].append(o[5])
    return xs, routing, kept


def states(tape: list, tokens, arch: dict, picks=None) -> list:
    """What a server would keep after reading ``tokens`` [rows, S], a
    layer at a time: dict(k, v [rows, c, S, d_h]: the cache rows; z, a
    [rows, (h + c) d_h] and v2 [rows, c d_h / 2]: the three windows
    after the last token; pick, margin, deficit [rows, S]). ``picks``
    [layers, rows, S] forces the routing (``forward``)."""
    _, routing, kept = forward(tape, [np.asarray(tokens)], arch, True,
                               None if picks is None else [picks])
    return [dict(k=k, v=v, z=z[:, -1], a=a[:, -1], v2=v2[:, -1],
                 pick=pick, margin=margin, deficit=deficit)
            for (k, v, z, a, v2), (pick, margin, deficit)
            in zip(kept[0], routing[0])]


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, rows_of_table, scale, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, scale.astype(jnp.float32), eps)
        return h @ rows_of_table.astype(jnp.float32).T


def logits(tape: list, tokens, arch: dict) -> np.ndarray:
    """[rows, S, vocab] float32: every position's logits (small sizes:
    the tests)."""
    x = forward(tape, [np.asarray(tokens)], arch)[0][0]
    return np.asarray(_logits(x, jnp.asarray(tape[0]),
                              jnp.asarray(tape[-1]),
                              float(arch["rms_norm_eps"])))


def choice_gaps(tape: list, batches: list, arch: dict) -> list:
    """For each [rows, S] token array: [rows, S, 2] float32 — how far
    the NEXT token's logit at each position lies below the best logit
    there (0 where the row continues with the reference's own greedy
    choice; the last column means nothing), and the position's smallest
    routing margin ``p_1 - p_2`` over the layers: where that is next to
    nothing the pick, and with it the logits, hang on the rounding."""
    table, scale = jnp.asarray(tape[0]), jnp.asarray(tape[-1])
    eps = float(arch["rms_norm_eps"])
    xs, routing, _ = forward(tape, batches, arch)
    out = []
    for tokens, x, routed in zip(batches, xs, routing):
        rows, s = tokens.shape
        nxt = np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        flat = x.reshape(rows * s, -1)
        chosen = jnp.asarray(nxt.reshape(-1), jnp.int32)
        gaps = []
        for i in range(0, rows * s, HEAD_CHUNK):
            best = picked = None
            for lo in range(0, table.shape[0], VOCAB_CHUNK):
                part = _logits(flat[i:i + HEAD_CHUNK],
                               table[lo:lo + VOCAB_CHUNK], scale, eps)
                at = chosen[i:i + HEAD_CHUNK] - lo
                mine = jnp.take_along_axis(
                    part, jnp.clip(at, 0, part.shape[1] - 1)[:, None],
                    1)[:, 0]
                mine = jnp.where((at >= 0) & (at < part.shape[1]), mine,
                                 -jnp.inf)
                top = jnp.max(part, -1)
                best = top if best is None else jnp.maximum(best, top)
                picked = mine if picked is None \
                    else jnp.maximum(picked, mine)
            gaps.append(best - picked)
        margin = np.min([m for _, m, _ in routed], axis=0)
        out.append(np.stack([
            np.asarray(jnp.concatenate(gaps)).reshape(rows, s), margin],
            axis=-1))
    return out
