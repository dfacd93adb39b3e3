"""The ops a latent-attention, sparse-expert decoder adds (rms_norm,
rotary_embedding, moe_router, moe_experts), each with its gradient,
against the functions of the plain reference
(chipbench/reference_joyai.py: no sort, no grouped product — every held
expert runs on every token and the routing masks the result); and the
expert layer's contract with the deployment: the parts that all the
shares of an expert-parallel group give, with the shared expert counted
once, add up to the uncut layer, at ANY routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench import reference_joyai as ref
from chipbench import reference_laguna
from paddle_tpu import layers
from paddle_tpu.core.registry import grad_var_name
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.moe_ops import held_experts_ffn

T, D, F, E, K = 24, 16, 12, 16, 4
ROUTING = dict(num_experts_per_tok=K,
               routed_scaling_factor=2.5)


def _run_op(op_type, feeds, attrs, outs, wrt, out_dtypes=None):
    """(program, output vars, feed) of one op over fed inputs, with the
    backward pass of sum(last output * cot) for the inputs in ``wrt``."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ins = {slot: layers.data(name, list(a.shape), dtype=str(a.dtype),
                                 append_batch_size=False,
                                 stop_gradient=name not in wrt)
               for slot, (name, a) in feeds.items()}
        helper = LayerHelper(op_type)
        out_vars = {s: helper.create_tmp_variable(
            (out_dtypes or {}).get(s, "float32")) for s in outs}
        helper.append_op(type=op_type, inputs=ins, outputs=out_vars,
                         attrs=attrs)
        first = out_vars[outs[-1]]
        cot = layers.data("cot", [-1], append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(
            layers.reshape(first, [-1]), cot))
        pt.append_backward(loss, program=main)
    return main, out_vars, {name: a for name, a in feeds.values()}


def _fetch(main, out_vars, feed, outs, wrt, cot):
    got = pt.Executor().run(
        main, feed=dict(feed, cot=cot.reshape(-1)),
        fetch_list=[out_vars[s] for s in outs]
        + [grad_var_name(n) for n in wrt])
    return got[:len(outs)], got[len(outs):]


def _close(got, want, what, rtol=2e-5, atol=2e-6):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol, err_msg=what)


def test_rms_norm_and_its_gradients():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, D).astype(np.float32)
    scale = rng.uniform(0.5, 1.5, D).astype(np.float32)
    cot = rng.randn(3, 5, D).astype(np.float32)
    main, ov, feed = _run_op(
        "rms_norm", {"X": ("x", x), "Scale": ("scale", scale)},
        {"epsilon": 1e-6}, ["Y"], ("x", "scale"))
    (y,), grads = _fetch(main, ov, feed, ["Y"], ("x", "scale"), cot)
    _close(y, ref.rms_norm(x, scale, 1e-6), "y")
    want = jax.grad(lambda x, s: jnp.sum(ref.rms_norm(x, s, 1e-6) * cot),
                    (0, 1))(x, scale)
    for name, g, w in zip(("dx", "dscale"), grads, want):
        _close(g, w, name, rtol=2e-4, atol=2e-5)


def test_rms_norm_keeps_bf16_in_and_f32_statistics():
    """Statistics in float32 whatever the input's width: a bf16 input
    of large entries must not overflow or lose the mean square."""
    from paddle_tpu.core.registry import OpRegistry
    x = (np.random.RandomState(1).randn(4, 256) * 300).astype(np.float32)

    class Ctx:
        extra = {}
        outputs = {}

        def input(self, slot):
            return {"X": jnp.asarray(x, jnp.bfloat16),
                    "Scale": jnp.ones(256)}[slot]

        def attr(self, name, default=None):
            return default

        def set_output(self, slot, value):
            self.outputs[slot] = value

    ctx = Ctx()
    OpRegistry.get("rms_norm").compute(ctx)
    y = ctx.outputs["Y"]
    assert y.dtype == jnp.bfloat16
    want = ref.rms_norm(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                        1.0, 1e-6)
    _close(y.astype(jnp.float32), want, "y", rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("shape", [(2, 3, 10, 8), (2, 1, 10, 8)])
def test_rotary_embedding_on_interleaved_pairs(shape):
    rng = np.random.RandomState(2)
    x = rng.randn(*shape).astype(np.float32)
    pos = (np.arange(10) * 37 + 5).astype(np.int64)   # fed, not 0..S-1
    cot = rng.randn(*shape).astype(np.float32)
    main, ov, feed = _run_op(
        "rotary_embedding", {"X": ("x", x), "Positions": ("pos", pos)},
        {"theta": 32000000.0}, ["Out"], ("x",))
    (y,), (dx,) = _fetch(main, ov, feed, ["Out"], ("x",), cot)
    _close(y, ref.rope(jnp.asarray(x), jnp.asarray(pos), 32000000.0), "y")
    want = jax.grad(lambda x: jnp.sum(ref.rope(
        x, jnp.asarray(pos), 32000000.0) * cot))(jnp.asarray(x))
    _close(dx, want, "dx", rtol=2e-4, atol=2e-5)
    # a rotation: norms of pairs survive, position 0 is the identity
    y0 = pt.Executor().run(main, feed=dict(
        feed, pos=np.zeros(10, np.int64), cot=cot.reshape(-1)),
        fetch_list=[ov["Out"]])[0]
    _close(y0, x, "position 0")
    _close(np.square(y).reshape(*shape[:-1], -1, 2).sum(-1),
           np.square(x).reshape(*shape[:-1], -1, 2).sum(-1), "norms",
           rtol=1e-4, atol=1e-5)


YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}


@pytest.mark.parametrize("params", [
    {"rope_type": "default", "rope_theta": 10000,
     "partial_rotary_factor": 1},
    {"rope_type": "default", "rope_theta": 10000,
     "partial_rotary_factor": 0.5},
    YARN,
], ids=["rotate-half", "half-of-the-head", "yarn-table-and-scale"])
def test_rotary_embedding_rotate_half_partial_and_given_table(params):
    """The op's rotate-half layout, a rotary width under the head's and
    a frequency table given as data with a scale on cos and sin, as
    ``models.decoder_moe`` asks for them from one ``rope_parameters``
    block, against the plain reference's own few lines."""
    from paddle_tpu.models.decoder_moe import _rope_keywords
    shape = (2, 3, 10, 16)
    rng = np.random.RandomState(3)
    x = rng.randn(*shape).astype(np.float32)
    pos = (np.arange(10) * 37 + 5).astype(np.int64)
    cot = rng.randn(*shape).astype(np.float32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xv = layers.data("x", list(shape), append_batch_size=False,
                         stop_gradient=False)
        pv = layers.data("pos", [10], dtype="int64",
                         append_batch_size=False)
        cv = layers.data("cot", list(shape), append_batch_size=False)
        out = layers.rotary_embedding(xv, pv, **_rope_keywords(params, 16))
        pt.append_backward(layers.reduce_sum(
            layers.elementwise_mul(out, cv)), program=main)
    y, dx = pt.Executor().run(
        main, feed={"x": x, "pos": pos, "cot": cot},
        fetch_list=[out, grad_var_name("x")])
    want = reference_laguna.rope(jnp.asarray(x), jnp.asarray(pos), params)
    _close(y, want, "y", rtol=2e-4, atol=2e-5)
    _close(dx, jax.grad(lambda x: jnp.sum(reference_laguna.rope(
        x, jnp.asarray(pos), params) * cot))(jnp.asarray(x)), "dx",
        rtol=2e-4, atol=2e-5)
    r = int(16 * params["partial_rotary_factor"])
    np.testing.assert_array_equal(y[..., r:], x[..., r:])
    # columns (i, i + r/2) turn together, scaled by the attention factor
    scale = params.get("attention_factor", 1.0)
    _close(np.square(y[..., :r // 2]) + np.square(y[..., r // 2:r]),
           scale ** 2 * (np.square(x[..., :r // 2])
                         + np.square(x[..., r // 2:r])), "norms",
           rtol=1e-4, atol=1e-5)


def test_yarn_table_is_the_closed_form():
    """theta 5e5, 64 rotary columns, factor 64 over an original 4096,
    beta_fast 64, beta_slow 1: pairs 0-5 keep theta^(-2i/64) (they turn
    more than 64 times over 4096 positions), pairs 16-31 take it over
    64, a linear ramp between."""
    from paddle_tpu.models.decoder_moe import yarn_inv_freq
    table = yarn_inv_freq(5e5, 64, 64, 4096, 64, 1)

    def turning(turns):
        return 64 * np.log(4096 / (2 * np.pi * turns)) / (2 * np.log(5e5))

    lo, hi = int(np.floor(turning(64))), int(np.ceil(turning(1)))
    assert (lo, hi) == (5, 16) and len(table) == 32
    plain = [5e5 ** (-2 * i / 64) for i in range(32)]
    np.testing.assert_allclose(table[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(table[16:], np.asarray(plain[16:]) / 64,
                               rtol=1e-12)
    for i in range(6, 16):
        keep = 1 - (i - 5) / 11
        assert table[i] == pytest.approx(
            plain[i] / 64 * (1 - keep) + plain[i] * keep, rel=1e-12)
    assert table[0] == 1.0
    assert table[31] == pytest.approx(4.709153362717455e-08, rel=1e-9)
    got, factor, r = reference_laguna.inv_freq(YARN, 128)
    np.testing.assert_allclose(got, table, rtol=1e-12)
    assert r == 64 and factor == pytest.approx(0.1 * np.log(64) + 1)


def _router_inputs(seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(T, D).astype(np.float32)
    w = (rng.randn(D, E) * 0.5).astype(np.float32)
    return x, w, rng


def _route(x, w, bias, wrt=("x", "w")):
    attrs = dict(top_k=K, routed_scaling_factor=2.5)
    feeds = {"X": ("x", x), "W": ("w", w)}
    if bias is not None:
        feeds["Bias"] = ("bias", bias)
    return _run_op("moe_router", feeds, attrs, ["TopIdx", "TopW"], wrt,
                   out_dtypes={"TopIdx": "int32"})


def test_moe_router_and_its_gradients():
    x, w, rng = _router_inputs(3)
    bias = rng.uniform(-0.1, 0.1, E).astype(np.float32)
    cot = rng.randn(T, K).astype(np.float32)
    main, ov, feed = _route(x, w, bias)
    (idx, weights), grads = _fetch(main, ov, feed, ["TopIdx", "TopW"],
                                   ("x", "w"), cot)
    want_idx, want_w = ref.route(x, w, bias, ROUTING)
    np.testing.assert_array_equal(idx, want_idx)
    assert idx.dtype == np.int32
    _close(weights, want_w, "weights")
    _close(weights.sum(-1), np.full(T, 2.5), "normalised and scaled",
           rtol=1e-5)
    want = jax.grad(lambda x, w: jnp.sum(
        ref.route(x, w, bias, ROUTING)[1] * cot), (0, 1))(x, w)
    for name, g, wg in zip(("dx", "dw"), grads, want):
        _close(g, wg, name, rtol=2e-4, atol=2e-5)


def test_selection_bias_chooses_and_does_not_weigh():
    """A bias that lifts two experts into every token's selection: the
    picks change, and the weights are still the picked experts' own
    scores, normalised — the bias appears in no weight."""
    x, w, _ = _router_inputs(4)
    bias = np.zeros(E, np.float32)
    bias[[5, 11]] = 10.0
    (plain_idx, _), _ = _fetch(*_route(x, w, None, wrt=()),
                               ["TopIdx", "TopW"], (), np.zeros((T, K)))
    (idx, weights), _ = _fetch(*_route(x, w, bias, wrt=()),
                               ["TopIdx", "TopW"], (), np.zeros((T, K)))
    assert all({5, 11} <= set(row) for row in idx.tolist())
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain_idx, -1))
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    picked = np.take_along_axis(scores, idx, -1)
    _close(weights, picked / picked.sum(-1, keepdims=True) * 2.5,
           "weights from the scores alone")


def _expert_weights(rng, held):
    return [(rng.randn(held * D, F) * 0.3).astype(np.float32),
            (rng.randn(held * D, F) * 0.3).astype(np.float32),
            (rng.randn(held * F, D) * 0.3).astype(np.float32)]


def _experts_op(x, idx, weights, mats, held, offset, total=E):
    names = ("x", "topw", "w_gate", "w_up", "w_down")
    feeds = {"X": ("x", x), "TopIdx": ("idx", idx), "TopW": ("topw", weights),
             "WGate": ("w_gate", mats[0]), "WUp": ("w_up", mats[1]),
             "WDown": ("w_down", mats[2])}
    attrs = dict(experts_total=total, experts_held=held,
                 expert_offset=offset, top_k=idx.shape[-1])
    return _run_op("moe_experts", feeds, attrs, ["Out"], names), names


@pytest.mark.parametrize("held,offset", [(4, 0), (4, 8), (16, 0), (3, 13)])
def test_moe_experts_and_its_gradients(held, offset):
    rng = np.random.RandomState(5 + held + offset)
    x = rng.randn(T, D).astype(np.float32)
    idx, weights = (np.asarray(a) for a in ref.route(
        x, (rng.randn(D, E) * 0.5).astype(np.float32),
        np.zeros(E, np.float32), ROUTING))
    mats = _expert_weights(rng, held)
    cot = rng.randn(T, D).astype(np.float32)
    (main, ov, feed), names = _experts_op(x, idx.astype(np.int32), weights,
                                          mats, held, offset)
    (out,), grads = _fetch(main, ov, feed, ["Out"], names, cot)
    _close(out, ref.routed_experts(x, idx, weights, *mats, held, offset),
           "out", rtol=2e-4, atol=2e-5)
    want = jax.grad(lambda x, tw, a, b, c: jnp.sum(ref.routed_experts(
        x, idx, tw, a, b, c, held, offset) * cot), (0, 1, 2, 3, 4))(
            x, weights, *mats)
    for name, g, w in zip(names, grads, want):
        _close(g, w, "d" + name, rtol=1e-3, atol=1e-4)


def test_moe_experts_counts_its_live_rows_and_the_tally_keeps_them():
    """LiveRows is the number of assignments to held experts (4..6 of
    16 here), BufferRows the rows of the blocks that held them (at this
    size one block: the worst case, tokens x 3); ``moe_rows_tally`` folds a
    step's counts into (live rows summed over the steps, steps, the
    last step's live rows, buffer rows summed over the steps)."""
    rng = np.random.RandomState(9)
    x = rng.randn(T, D).astype(np.float32)
    idx = np.stack([rng.permutation(E)[:K] for _ in range(T)])
    weights = rng.uniform(0.1, 1.0, (T, K)).astype(np.float32)
    feeds = {"X": ("x", x), "TopIdx": ("idx", idx.astype(np.int32)),
             "TopW": ("topw", weights)}
    feeds.update(zip(("WGate", "WUp", "WDown"), zip(
        ("w_gate", "w_up", "w_down"), _expert_weights(rng, 3))))
    main, ov, feed = _run_op(
        "moe_experts", feeds, dict(experts_total=E, experts_held=3,
                                   expert_offset=4, top_k=K),
        ["LiveRows", "BufferRows", "Out"], ())
    live, buf = pt.Executor().run(main, feed=dict(
        feed, cot=np.zeros(T * D, np.float32)),
        fetch_list=[ov["LiveRows"], ov["BufferRows"]])
    want = int(np.sum((idx >= 4) & (idx < 7)))
    assert 0 < want < T * 3 and float(live) == want
    assert moe_ops.block_rows(T, K, 3, E) == T * 3
    assert float(buf) == T * 3
    main, ov, feed = _run_op(
        "moe_rows_tally",
        {"Tally": ("tally", np.array([40., 3., 9., 216.], np.float32)),
         "LiveRows": ("live", np.float32(want)),
         "BufferRows": ("buf", np.float32(72))}, {}, ["TallyOut"], ())
    out = pt.Executor().run(main, feed=dict(feed, cot=np.zeros(4, np.float32)),
                            fetch_list=[ov["TallyOut"]])[0]
    np.testing.assert_array_equal(out, [40 + want, 4, want, 288])


def _ffn_over(x, idx, weights, mats, held, offset, total=E):
    d, f = x.shape[-1], mats[0].shape[-1]
    return held_experts_ffn(
        jnp.asarray(x), jnp.asarray(idx, jnp.int32), jnp.asarray(weights),
        mats[0].reshape(held, d, f), mats[1].reshape(held, d, f),
        mats[2].reshape(held, f, d), expert_offset=offset,
        experts_total=total)


@pytest.mark.parametrize("experts,shares,width,top_k,biased", [
    (16, 4, F, K, True), (256, 32, 512, 8, False)],
    ids=["4-shares-of-4-selection-bias", "32-shares-of-8-no-bias-512-wide"])
def test_shares_of_a_group_add_up_to_the_uncut_layer(experts, shares, width,
                                                     top_k, biased):
    """The shares' routed parts plus the shared expert ONCE equal the
    uncut reference layer (router, every expert, shared expert): 16
    experts in 4 shares under a selection bias (reference_joyai), and
    256 experts 512 wide in the 32 shares of 8 of the laguna-xs2
    deployment, selected by the scores alone (reference_laguna)."""
    rng = np.random.RandomState(6)
    held = experts // shares
    x = rng.randn(T, D).astype(np.float32)
    router = (rng.randn(D, experts) * 0.5).astype(np.float32)
    mats = [(rng.randn(experts * D, width) * 0.3).astype(np.float32),
            (rng.randn(experts * D, width) * 0.3).astype(np.float32),
            (rng.randn(experts * width, D) * 0.3 / np.sqrt(width / F))
            .astype(np.float32)]
    shared = [(rng.randn(D, F) * 0.3).astype(np.float32),
              (rng.randn(D, F) * 0.3).astype(np.float32),
              (rng.randn(F, D) * 0.3).astype(np.float32)]
    m = dict(num_experts_per_tok=top_k, routed_scaling_factor=2.5,
             n_routed_experts=experts, experts_held=experts,
             expert_offset=0)
    if biased:
        bias = rng.uniform(-0.1, 0.1, experts).astype(np.float32)
        uncut = ref.moe_ffn(jnp.asarray(x),
                            [router, bias] + mats + shared, m)
        idx, weights = ref.route(x, router, bias, m)
    else:
        uncut = reference_laguna.moe_ffn(jnp.asarray(x),
                                         [router] + mats + shared, m)
        idx, weights = reference_laguna.route(x, router, m)
    total = ref.gated_ffn(jnp.asarray(x), *shared)
    for share in range(shares):
        rows = slice(share * held * D, (share + 1) * held * D)
        down = slice(share * held * width, (share + 1) * held * width)
        part = [mats[0][rows], mats[1][rows], mats[2][down]]
        total = total + _ffn_over(x, idx, weights, part, held,
                                  share * held, experts)
    _close(total, uncut, "sum of the shares", rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("case", ["every_pick_held", "no_pick_held"])
def test_no_token_is_dropped_at_the_extreme_routings(case):
    """Held experts 4..7 of 16, top-4. Worst case: EVERY token routes
    all four picks to the held experts (rows = tokens x 4, the whole
    buffer live). Best case: no token routes to them (no live row, the
    part is exactly zero). Both exact against the reference."""
    rng = np.random.RandomState(7)
    x = rng.randn(T, D).astype(np.float32)
    mats = _expert_weights(rng, 4)
    if case == "every_pick_held":
        idx = np.stack([rng.permutation(4) + 4 for _ in range(T)])
    else:
        idx = np.stack([rng.permutation(12)[:4] for _ in range(T)])
        idx = np.where(idx >= 4, idx + 4, idx)       # skips 4..7
    weights = rng.uniform(0.1, 1.0, (T, K)).astype(np.float32)
    got = _ffn_over(x, idx, weights, mats, 4, 4)
    want = ref.routed_experts(x, idx, weights, *mats, 4, 4)
    _close(got, want, case, rtol=2e-4, atol=2e-5)
    if case == "no_pick_held":
        assert not np.asarray(got).any()
    else:
        assert np.abs(np.asarray(got)).min(axis=-1).max() > 0
    grads = jax.grad(lambda x: jnp.sum(_ffn_over(
        x, idx, weights, mats, 4, 4) ** 2))(jnp.asarray(x))
    want_g = jax.grad(lambda x: jnp.sum(ref.routed_experts(
        x, idx, weights, *mats, 4, 4) ** 2))(jnp.asarray(x))
    _close(grads, want_g, case + " dx", rtol=1e-3, atol=1e-4)


def test_fewer_held_experts_than_picks_shrinks_the_buffer():
    """2 held experts under top-4: at most 2 of a token's distinct picks
    are held, so tokens x 2 rows hold the worst case — and do."""
    rng = np.random.RandomState(8)
    x = rng.randn(T, D).astype(np.float32)
    mats = _expert_weights(rng, 2)
    idx = np.stack([np.concatenate([[6, 7], rng.permutation(6)[:2]])
                    [rng.permutation(4)] for _ in range(T)])
    weights = rng.uniform(0.1, 1.0, (T, K)).astype(np.float32)
    got = _ffn_over(x, idx, weights, mats, 2, 6)
    _close(got, ref.routed_experts(x, idx, weights, *mats, 2, 6),
           "two held under top-4", rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shape,want", [
    ((8192, 8, 8, 256), 8192),         # laguna-xs2.train-s8192: 8 blocks
    ((4096, 8, 8, 256), 4096),         # joyai-llm-flash.train-ep32: 8 blocks
    ((512, 2, 2, 16), 512),
    ((1024, 4, 2, 64), 512),
    ((1000, 8, 8, 256), 1024),         # up to a multiple of 512
    ((24, 4, 16, 16), 96),             # every expert held: the worst case
    ((512, 4, 2, 16), 1024),           # a quarter of the picks held: too
    ((24, 4, 2, 16), 48),              # the worst case under 512 rows
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_block_rows_from_the_shapes_alone(shape, want):
    """Four times the uniform routing's rows, up to a multiple of 512,
    and no more than the worst case."""
    assert moe_ops.block_rows(*shape) == want


# layers whose block is under their worst case: experts 6 and 7 of 16
# under top-2 over 512 tokens (512-row blocks, 1,024 rows at the worst),
# experts 9 and 10 of 64 under top-4 over 1,024 tokens (512, 2,048) and
# under top-2 over 600 tokens (512, 1,200)
BLOCKED = {"2-of-16-top-2": (512, 2, 2, 6, 16),
           "2-of-64-top-4": (1024, 4, 2, 9, 64),
           # 1,200 assignments, every one held at the worst: the third
           # block runs past them, into the padding of the sort's order
           "2-of-64-top-2-600-tokens": (600, 2, 2, 9, 64)}


def _routing_with(rng, n_live, tokens, k, held, offset, total):
    """TopIdx [tokens, k] of distinct picks a token, ``n_live`` of them
    in [offset, offset + held), spread over shuffled tokens."""
    count = np.full(tokens, n_live // tokens)
    count[:n_live % tokens] += 1
    count = count[rng.permutation(tokens)]
    assert count.max() <= min(k, held)
    idx = []
    for c in count:
        absent = rng.permutation(total - held)[:k - c]
        picks = np.concatenate([offset + rng.permutation(held)[:c],
                                np.where(absent >= offset, absent + held,
                                         absent)])
        idx.append(picks[rng.permutation(k)])
    idx = np.stack(idx).astype(np.int32)
    assert np.sum((idx >= offset) & (idx < offset + held)) == n_live
    return idx


def _block_cases():
    for name, (tokens, k, held, _offset, total) in BLOCKED.items():
        block = moe_ops.block_rows(tokens, k, held, total)
        rows = tokens * min(k, held)
        assert block < rows
        for n_live in sorted({0, 1, rows} | {
                n for edge in range(block, rows, block)
                for n in (edge - 1, edge, edge + 1)}):
            yield pytest.param(name, n_live, -(-n_live // block) * block,
                               id=f"{name}-{n_live}-live")


@pytest.mark.parametrize("layer,n_live,buffer_rows", list(_block_cases()))
def test_every_count_of_blocks_gives_the_worst_cases_sums(layer, n_live,
                                                          buffer_rows):
    """At each block's edge, with no pick held and with every pick held
    (hand-built TopIdx): Out and the gradients for X, TopW and the
    three matrices equal the one worst-case pass's to float32 rounding
    and the reference's to the file's limits; LiveRows and BufferRows
    by hand."""
    tokens, k, held, offset, total = BLOCKED[layer]
    rng = np.random.RandomState(n_live)
    x = rng.randn(tokens, D).astype(np.float32)
    idx = _routing_with(rng, n_live, tokens, k, held, offset, total)
    weights = rng.uniform(0.1, 1.0, (tokens, k)).astype(np.float32)
    mats = _expert_weights(rng, held)
    cot = rng.randn(tokens, D).astype(np.float32)
    names = ("x", "topw", "w_gate", "w_up", "w_down")
    feeds = dict(zip(("X", "TopW", "WGate", "WUp", "WDown"),
                     zip(names, (x, weights, *mats))), TopIdx=("idx", idx))
    main, ov, feed = _run_op(
        "moe_experts", feeds,
        dict(experts_total=total, experts_held=held, expert_offset=offset,
             top_k=k), ["LiveRows", "BufferRows", "Out"], names)
    (live, buf, out), grads = _fetch(
        main, ov, feed, ["LiveRows", "BufferRows", "Out"], names, cot)
    assert (float(live), float(buf)) == (n_live, buffer_rows)

    def one_pass(x, tw, a, b, c):
        return moe_ops._whole_buffer(
            offset, x, jnp.asarray(idx), tw, a.reshape(held, D, F),
            b.reshape(held, D, F), c.reshape(held, F, D))

    def reference(x, tw, a, b, c):
        return ref.routed_experts(x, idx, tw, a, b, c, held, offset)

    for what, fn, (rtol, atol), (g_rtol, g_atol) in [
            ("the worst-case pass", one_pass, (1e-6, 1e-6), (1e-5, 5e-5)),
            ("the reference", reference, (2e-4, 2e-5), (1e-3, 1e-4))]:
        want, pull = jax.vjp(fn, x, weights, *mats)
        _close(out, want, f"out against {what}", rtol=rtol, atol=atol)
        for name, g, w in zip(names, grads, pull(jnp.asarray(cot))):
            _close(g, w, f"d{name} against {what}", rtol=g_rtol, atol=g_atol)


def test_what_a_dead_row_holds_reaches_no_gradient(monkeypatch):
    """On the chip the grouped products write nothing into a row past
    the live ones, which then holds whatever the buffer held. With NaN
    planted there, Out and every gradient are finite and equal the
    clean run's."""
    tokens, k, held, offset, total = BLOCKED["2-of-64-top-4"]
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(tokens, D), jnp.float32)
    idx = _routing_with(rng, 700, tokens, k, held, offset, total)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)), jnp.float32)
    mats = [jnp.asarray(m) for m in _expert_weights(rng, held)]

    def run():
        return jax.value_and_grad(lambda *a: jnp.sum(_ffn_over(
            a[0], idx, a[1], a[2:], held, offset, total) ** 2),
            (0, 1, 2, 3, 4))(x, weights, *mats)

    clean = run()
    grouped = jax.lax.ragged_dot

    def leaves_dead_rows_dirty(a, w, sizes, **kw):
        dead = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(dead[:, None], jnp.nan, grouped(a, w, sizes, **kw))

    monkeypatch.setattr(jax.lax, "ragged_dot", leaves_dead_rows_dirty)
    dirty = run()
    for want, got in zip(jax.tree.leaves(clean), jax.tree.leaves(dirty)):
        assert np.isfinite(np.asarray(got)).all()
        _close(got, want, "with NaN in the dead rows", rtol=1e-6, atol=1e-6)


def _shapes_in(jaxpr):
    """(primitive, shape) of everything a jaxpr computes, the jaxprs
    inside its equations' parameters included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes_in(sub)


def _both_passes(tokens, k, held, total):
    """The jaxpr of an expert layer's forward and backward passes."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(tokens, D), jnp.float32)
    idx = np.stack([rng.permutation(total)[:k] for _ in range(tokens)])
    weights = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    mats = [jnp.asarray(m) for m in _expert_weights(rng, held)]

    def loss(x, weights, *mats):
        return jnp.sum(_ffn_over(x, idx, weights, mats, held, 0, total))

    return jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3, 4)))(
        x, weights, *mats).jaxpr


def test_a_blocked_layer_has_no_pass_as_long_as_the_worst_case():
    """Forward and backward of a layer of 512-row blocks whose worst
    case is 2,048 rows and whose routing makes 4,096 assignments: no
    array with either as its leading dimension beside a feature width
    (the sort's keys and ``order`` and the gradient of TopW have none),
    and both passes loop over the grouped products."""
    tokens, k, held, _offset, total = BLOCKED["2-of-64-top-4"]
    assert (moe_ops.block_rows(tokens, k, held, total), tokens * held,
            tokens * k) == (512, 2048, 4096)
    shapes = list(_shapes_in(_both_passes(tokens, k, held, total)))
    prims = [p for p, _ in shapes]
    assert "while" in prims and "ragged_dot_general" in prims
    assert "remat2" not in prims and "cond" not in prims
    long = [(p, s) for p, s in shapes
            if len(s) > 1 and s[0] in (2048, 4096) and s[-1] in (D, F)]
    assert not long, long
    assert any(s == (512, D) for _, s in shapes)


def test_a_layer_with_every_expert_held_traces_as_one_pass():
    """Every assignment is live where every expert is held: the block
    is the worst case, and the layer is the one pass it was — under
    ``jax.checkpoint``, no loop, no branch."""
    prims = [p for p, _ in _shapes_in(_both_passes(512, K, E, E))]
    assert "remat2" in prims
    assert not {"while", "cond"} & set(prims)


def test_moe_experts_refuses_a_range_outside_the_layer():
    x = np.zeros((T, D), np.float32)
    idx = np.zeros((T, K), np.int32)
    (main, ov, feed), _ = _experts_op(
        x, idx, np.zeros((T, K), np.float32),
        _expert_weights(np.random.RandomState(0), 4), 4, 14)
    with pytest.raises(Exception, match="are not among"):
        _fetch(main, ov, feed, ["Out"], (), np.zeros((T, D), np.float32))


def test_cost_model_books_the_new_ops():
    from paddle_tpu.models import decoder_moe
    main, startup, fetch = decoder_moe.build_train(
        trg_vocab=96, max_len=16, hidden_size=32, num_attention_heads=2,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=64,
        moe_intermediate_size=24, n_routed_experts=8, experts_held=2,
        num_experts_per_tok=2, num_hidden_layers=2)
    from paddle_tpu.analysis.cost_model import program_cost
    cost = program_cost(main.desc, feed_shapes={
        "src_ids": [2, 16, 1], "trg_ids": [2, 16, 1],
        "trg_labels": [2, 16, 1], "pos_ids": [16]})
    by_type = {}
    for c in cost.ops:
        by_type.setdefault(c.op_type, []).append(c)
    tokens = 32
    assert [c.flops for c in by_type["moe_router"]] == [2 * tokens * 32 * 8] * 2
    # expectation under uniform routing: tokens * top_k * held / total
    assert [c.flops for c in by_type["moe_experts"]] == \
        [6 * (tokens * 2 * 2 // 8) * 32 * 24] * 2
    # attention: QK^T at the key width (24), PV at the value width (16)
    assert all(c.flops == 2 * 2 * 2 * 16 * 16 * (24 + 16)
               + 5 * 2 * 2 * 16 * 16
               for c in by_type["scaled_dot_product_attention"])
    assert all(c.flops > 0 for t in ("rms_norm", "rotary_embedding")
               for c in by_type[t])
    assert not cost.unresolved
