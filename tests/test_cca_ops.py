"""The compressed-convolutional-attention and MLP-router ops
(ops/cca_ops.py) against plain numpy loops: the grouped causal
convolution and its carried window, a padded prompt, the in-place update
against a longer prefill, the mean join and the head norms, the router's
picks, weights, carried term and counts, rows that are no token, the
depthwise pair at two taps as the shift of a value, the rotary embedding
at a decode step's own positions, and what the shape inference, the
cost model and the counters say of them."""
import collections
import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.ir import OpDesc
from paddle_tpu.core.registry import run_op
from paddle_tpu.observability import default_registry

HEADS, WIDTH, TAPS = 3, 8, 2
C = HEADS * WIDTH


def _conv_inputs(s, seed=0, n=2, taps=TAPS):
    rng = np.random.default_rng(seed)
    return dict(x=rng.normal(0, 1, (n, s, C)).astype(np.float32),
                w=rng.normal(0, 1, (taps * C, WIDTH)).astype(np.float32),
                bias=rng.normal(0, 1, C).astype(np.float32))


def _conv_op(t, lengths):
    op = OpDesc("grouped_causal_conv1d",
                {"X": ["x"], "W": ["w"], "Bias": ["bias"], "Length": ["n"]},
                {"Out": ["o"], "State": ["s"]}, {"heads": HEADS})
    env = {k: jnp.asarray(v) for k, v in t.items()}
    env["n"] = jnp.asarray(lengths, jnp.int64)
    out = run_op(op, env, {})
    return np.asarray(out["o"]), np.asarray(out["s"])


def _conv_step(window, t, pos):
    op = OpDesc("grouped_conv_state_update",
                {"State": ["s"], "X": ["x"], "W": ["w"], "Bias": ["bias"]},
                {"Out": ["o"], "StateOut": ["s"]}, {"heads": HEADS})
    out = run_op(op, {"s": jnp.asarray(window),
                      "x": jnp.asarray(t["x"][:, pos:pos + 1]),
                      "w": jnp.asarray(t["w"]),
                      "bias": jnp.asarray(t["bias"])}, {})
    return np.asarray(out["o"]), np.asarray(out["s"])


def _conv_loop(t, row, pos, taps=TAPS):
    """Position ``pos`` of row ``row``, one head and one tap at a time:
    tap k reads the input taps - 1 - k rows back through its own
    [WIDTH, WIDTH] matrix."""
    w = t["w"].reshape(taps, HEADS, WIDTH, WIDTH)
    want = t["bias"].astype(np.float64).copy()
    for k in range(taps):
        src = pos - (taps - 1 - k)
        if src < 0:
            continue
        for h in range(HEADS):
            cols = slice(h * WIDTH, (h + 1) * WIDTH)
            want[cols] += t["x"][row, src, cols].astype(np.float64) @ w[k, h]
    return want


# a prompt that fills its bucket, padded ones, a single real token
@pytest.mark.parametrize("s,lengths,taps", [
    (10, [10, 6], 2), (5, [2, 1], 2), (8, [3, 8], 3), (4, [4, 4], 2)])
def test_grouped_causal_conv1d_and_its_window(s, lengths, taps):
    t = _conv_inputs(s, seed=s, taps=taps)
    out, window = _conv_op(t, lengths)
    for row in range(2):
        for pos in range(s):
            np.testing.assert_allclose(out[row, pos],
                                       _conv_loop(t, row, pos, taps),
                                       rtol=1e-5, atol=1e-5)
        # the last taps - 1 REAL inputs, oldest first, zeros before 0:
        # the pad rows beyond Length leave nothing in it
        real = np.zeros((taps - 1, C), np.float32)
        for k in range(taps - 1):
            src = lengths[row] - (taps - 1) + k
            if src >= 0:
                real[k] = t["x"][row, src]
        np.testing.assert_array_equal(window[row].reshape(taps - 1, C),
                                      real)


@pytest.mark.parametrize("n_prompt,k_steps", [(1, 5), (6, 3), (2, 2)])
def test_grouped_prefill_then_updates_is_the_longer_prefill(n_prompt,
                                                            k_steps):
    total = n_prompt + k_steps
    t = _conv_inputs(total + 3, seed=total)       # 3 pad rows of junk
    want_out, want_window = _conv_op(t, [total, total])
    _, window = _conv_op(t, [n_prompt, n_prompt])
    for pos in range(n_prompt, total):
        out, window = _conv_step(window, t, pos)
        np.testing.assert_allclose(out[:, 0], want_out[:, pos], rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_array_equal(window, want_window)


def test_grouped_products_take_bfloat16_operands_and_sum_in_float32():
    t = _conv_inputs(6, seed=1)
    t16 = {k: jnp.asarray(v).astype(jnp.bfloat16) for k, v in t.items()}
    wide = {k: np.asarray(v.astype(jnp.float32)) for k, v in t16.items()}
    op = OpDesc("grouped_causal_conv1d",
                {"X": ["x"], "W": ["w"], "Bias": ["bias"], "Length": ["n"]},
                {"Out": ["o"], "State": ["s"]}, {"heads": HEADS})
    out = run_op(op, dict(t16, n=jnp.asarray([6, 6])), {})
    assert out["o"].dtype == jnp.bfloat16 and out["s"].dtype == jnp.bfloat16
    want = np.stack([[_conv_loop(wide, r, p) for p in range(6)]
                     for r in range(2)])
    got = np.asarray(out["o"].astype(jnp.float32))
    # one rounding of the float32 sum to bfloat16, no more
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()


# -- the depthwise pair at two taps, as a shift ---------------------------

def test_two_taps_one_and_zero_shift_a_value_by_one_token():
    """models/cca_moe.py's shifted value: ops/ssm_ops.py's depthwise
    pair under W = [[1], [0]], bias 0, IS u_(t-1), and the window it
    carries the last REAL row."""
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (1, 6, 4)).astype(np.float32)
    w = np.stack([np.ones(4), np.zeros(4)]).astype(np.float32)
    env = {"x": jnp.asarray(x), "w": jnp.asarray(w),
           "bias": jnp.zeros(4), "n": jnp.asarray([4])}
    out = run_op(OpDesc(
        "causal_conv1d",
        {"X": ["x"], "W": ["w"], "Bias": ["bias"], "Length": ["n"]},
        {"Out": ["o"], "State": ["s"]}, {}), env, {})
    np.testing.assert_array_equal(np.asarray(out["o"])[0, 1:], x[0, :-1])
    assert (np.asarray(out["o"])[0, 0] == 0).all()
    np.testing.assert_array_equal(np.asarray(out["s"])[0], x[0, 3])
    step = run_op(OpDesc(
        "conv_state_update",
        {"State": ["s"], "X": ["x1"], "W": ["w"], "Bias": ["bias"]},
        {"Out": ["o"], "StateOut": ["s"]}, {}),
        dict(env, s=out["s"], x1=jnp.asarray(x[:, 4:5])), {})
    np.testing.assert_array_equal(np.asarray(step["o"])[0, 0], x[0, 3])
    np.testing.assert_array_equal(np.asarray(step["s"])[0], x[0, 4])


# -- the mean join and the head norms --------------------------------------

def test_cca_qk_mix_against_a_loop():
    heads, kv, width = 4, 2, 8
    rng = np.random.default_rng(5)
    z = rng.normal(0, 1, (2, 3, (heads + kv) * width)).astype(np.float32)
    b = rng.normal(0, 1, z.shape).astype(np.float32)
    tau = np.asarray([0.7, 1.3], np.float32)
    out = run_op(OpDesc("cca_qk_mix", {"Z": ["z"], "B": ["b"],
                                       "Tau": ["tau"]},
                        {"Q": ["q"], "K": ["k"]},
                        {"heads": heads, "kv_heads": kv}),
                 {"z": jnp.asarray(z), "b": jnp.asarray(b),
                  "tau": jnp.asarray(tau)}, {})
    q, k = np.asarray(out["q"]), np.asarray(out["k"])
    assert q.shape == (2, 3, heads * width) and k.shape == (2, 3, kv * width)
    group = heads // kv

    def head(t, j):
        return t[j * width:(j + 1) * width].astype(np.float64)

    for row in range(2):
        for pos in range(3):
            zt, bt = z[row, pos], b[row, pos]
            for h in range(heads):
                c = h // group
                v = head(bt, h) + (head(zt, h) + head(zt, heads + c)) / 2
                np.testing.assert_allclose(
                    head(q[row, pos], h),
                    math.sqrt(width) * v / np.linalg.norm(v), rtol=1e-5)
            for c in range(kv):
                mean_q = np.mean([head(zt, c * group + g)
                                  for g in range(group)], axis=0)
                v = head(bt, heads + c) + (mean_q + head(zt, heads + c)) / 2
                np.testing.assert_allclose(
                    head(k[row, pos], c),
                    tau[c] * math.sqrt(width) * v / np.linalg.norm(v),
                    rtol=1e-5)


# -- the router --------------------------------------------------------------

D, HID, EXPERTS = 12, 6, 5


def _router_inputs(seed, n, s):
    rng = np.random.default_rng(seed)

    def m(*shape):
        return rng.normal(0, 1, shape).astype(np.float32)

    return dict(x=m(n, s, D), r_prev=m(n, s, HID), wd=m(D, HID),
                gamma=np.asarray([0.5], np.float32), w1=m(HID, HID),
                b1=m(HID), w2=m(HID, HID), b2=m(HID), w3=m(HID, EXPERTS),
                beta=np.zeros(EXPERTS, np.float32))


def _router_op(t, lengths, carried=True):
    inputs = {"X": ["x"], "WDown": ["wd"], "W1": ["w1"], "B1": ["b1"],
              "W2": ["w2"], "B2": ["b2"], "W3": ["w3"],
              "SelectBias": ["beta"], "Length": ["n"]}
    if carried:
        inputs.update(RPrev=["r_prev"], Gamma=["gamma"])
    op = OpDesc("mlp_router", inputs,
                {"TopIdx": ["i"], "TopW": ["p"], "R": ["r"],
                 "Counts": ["c"]}, {})
    env = {k: jnp.asarray(v) for k, v in t.items()}
    env["n"] = jnp.asarray(lengths, jnp.int64)
    out = run_op(op, env, {})
    return {k: np.asarray(v) for k, v in out.items()}


def _gelu(v):
    return 0.5 * v * (1.0 + np.vectorize(math.erf)(v / math.sqrt(2.0)))


def _router_loop(t, row, pos, carried=True):
    f = {k: v.astype(np.float64) for k, v in t.items()}
    r = f["x"][row, pos] @ f["wd"]
    if carried:
        r = r + f["gamma"][0] * f["r_prev"][row, pos]
    h = _gelu(_gelu(r @ f["w1"] + f["b1"]) @ f["w2"] + f["b2"])
    s = h @ f["w3"]
    p = np.exp(s - s.max())
    p /= p.sum()
    return r, p, int(np.argmax(p + f["beta"]))


@pytest.mark.parametrize("carried", [True, False])
def test_mlp_router_against_a_loop(carried):
    t = _router_inputs(7, 2, 4)
    out = _router_op(t, [4, 4], carried)
    sent = np.zeros(EXPERTS, np.int64)
    for row in range(2):
        for pos in range(4):
            r, p, pick = _router_loop(t, row, pos, carried)
            np.testing.assert_allclose(out["r"][row, pos], r, rtol=1e-5,
                                       atol=1e-5)
            assert out["i"][row, pos, 0] == pick
            np.testing.assert_allclose(out["p"][row, pos, 0], p[pick],
                                       rtol=1e-4)
            sent[pick] += 1
    np.testing.assert_array_equal(out["c"][:-1], sent)
    assert out["c"][-1] == np.count_nonzero(sent)


def test_the_balancing_bias_moves_the_pick_and_not_the_weight():
    t = _router_inputs(9, 1, 3)
    plain = _router_op(t, [3])
    t["beta"] = np.asarray([0, 0, 10, 0, 0], np.float32)
    biased = _router_op(t, [3])
    assert (biased["i"] == 2).all()
    for pos in range(3):
        _, p, _ = _router_loop(dict(t, beta=np.zeros(EXPERTS)), 0, pos)
        np.testing.assert_allclose(biased["p"][0, pos, 0], p[2], rtol=1e-4)
    np.testing.assert_array_equal(plain["r"], biased["r"])


# a padded prompt [n, S]; a decode step [slots, 1] with empty slots
@pytest.mark.parametrize("n,s,lengths", [(2, 5, [3, 5]), (4, 1, [7, 0, 0, 2]),
                                         (3, 1, [0, 0, 0])])
def test_rows_that_are_no_token_get_id_minus_one_and_add_no_live_row(
        n, s, lengths):
    t = _router_inputs(n + s, n, s)
    out = _router_op(t, lengths)
    live = np.arange(s)[None, :] < np.asarray(lengths)[:, None]
    assert (out["i"][..., 0][~live] == -1).all()
    assert (out["p"][..., 0][~live] == 0).all()
    assert (out["i"][..., 0][live] >= 0).all()
    assert out["c"][:-1].sum() == live.sum()
    # the expert layer sends such a row nowhere and tallies none
    rng = np.random.default_rng(1)
    f = 4
    mats = {"g": rng.normal(0, 1, (EXPERTS * D, f)),
            "u": rng.normal(0, 1, (EXPERTS * D, f)),
            "d": rng.normal(0, 1, (EXPERTS * f, D))}
    env = {k: jnp.asarray(v, jnp.float32) for k, v in mats.items()}
    env.update(x=jnp.asarray(t["x"]), i=jnp.asarray(out["i"]),
               p=jnp.asarray(out["p"]))
    moe = run_op(OpDesc(
        "moe_experts", {"X": ["x"], "TopIdx": ["i"], "TopW": ["p"],
                        "WGate": ["g"], "WUp": ["u"], "WDown": ["d"]},
        {"Out": ["o"], "LiveRows": ["l"], "BufferRows": ["b"]},
        {"experts_total": EXPERTS, "experts_held": EXPERTS,
         "expert_offset": 0, "top_k": 1}), env, {})
    assert float(moe["l"]) == live.sum()
    assert (np.asarray(moe["o"])[~live] == 0).all()
    if live.any():
        assert np.abs(np.asarray(moe["o"])[live]).min() > 0


# -- the rotary embedding at a decode step's own positions ---------------------

def test_rotary_at_each_slots_own_position_is_the_prompts_row():
    """A decode step moves the slots onto the op's row axis: slot s at
    position p_s is turned as row p_s of a prompt is."""
    rng = np.random.default_rng(2)
    heads, width, r = 2, 8, 4
    prompt = rng.normal(0, 1, (1, heads, 9, width)).astype(np.float32)
    attrs = {"theta": 5e6, "layout": "half", "rotary_dim": r}
    op = OpDesc("rotary_embedding", {"X": ["x"], "Positions": ["p"]},
                {"Out": ["o"]}, attrs)
    whole = np.asarray(run_op(op, {"x": jnp.asarray(prompt),
                                   "p": jnp.arange(9)}, {})["o"])
    positions = np.asarray([7, 0, 3])
    step = np.stack([prompt[0, :, p] for p in positions], axis=1)
    got = np.asarray(run_op(op, {"x": jnp.asarray(step),
                                 "p": jnp.asarray(positions)}, {})["o"])
    for s, p in enumerate(positions):
        np.testing.assert_allclose(got[:, s], whole[0, :, p], rtol=1e-6)
    # the columns past the rotary part pass through
    np.testing.assert_array_equal(got[..., r:], step[..., r:])


# -- what the build, the cost model and the counters read ----------------------

def _program_with_the_ops():
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        def data(name, shape, dtype="float32"):
            return layers.data(name, shape, dtype=dtype,
                               append_batch_size=False)
        x = data("x", [2, 12, C], "bfloat16")
        w, bias = data("w", [TAPS * C, WIDTH], "bfloat16"), \
            data("bias", [C], "bfloat16")
        length = data("length", [2], "int64")
        out, window = layers.grouped_causal_conv1d(x, w, bias, length, HEADS)
        held = data("held", [2, (TAPS - 1) * C], "bfloat16")
        x1 = data("x1", [2, 1, C], "bfloat16")
        o1 = layers.grouped_conv_state_update(held, x1, w, bias, HEADS)
        q, k = layers.cca_qk_mix(x, out, data("tau", [1]), 2, 1)
        u = data("u", [2, 12, D], "bfloat16")
        arrays = dict(WDown=data("wd", [D, HID]), W1=data("w1", [HID, HID]),
                      B1=data("b1", [HID]), W2=data("w2", [HID, HID]),
                      B2=data("b2", [HID]), W3=data("w3", [HID, EXPERTS]),
                      SelectBias=data("beta", [EXPERTS]))
        idx, weights, r, counts = layers.mlp_router(u, length, arrays)
    return main, dict(out=out, window=window, o1=o1, held=held, q=q, k=k,
                      idx=idx, weights=weights, r=r, counts=counts)


@pytest.mark.parametrize("name,shape,dtype", [
    ("out", [2, 12, C], "bfloat16"),
    ("window", [2, (TAPS - 1) * C], "bfloat16"),
    ("o1", [2, 1, C], "bfloat16"),
    ("held", [2, (TAPS - 1) * C], "bfloat16"),
    ("q", [2, 12, 2 * WIDTH], "bfloat16"),
    ("k", [2, 12, WIDTH], "bfloat16"),
    ("idx", [2, 12, 1], "int32"),
    ("weights", [2, 12, 1], "float32"),
    ("r", [2, 12, HID], "float32"),
    ("counts", [EXPERTS + 1], "int32")])
def test_shape_inference_gives_every_output_its_shape_and_width(
        name, shape, dtype):
    _, v = _program_with_the_ops()
    assert list(v[name].shape) == shape and v[name].dtype == dtype


def test_cost_model_books_the_grouped_taps_the_join_and_the_router():
    from paddle_tpu.analysis import cost_model
    main, _ = _program_with_the_ops()
    by_type = {row.op_type: row
               for row in cost_model.program_cost(main, batch=1).ops}
    rows = 2 * 12
    assert by_type["grouped_causal_conv1d"].flops == \
        2 * rows * TAPS * C * WIDTH
    assert by_type["grouped_conv_state_update"].flops == \
        2 * 2 * TAPS * C * WIDTH
    assert by_type["cca_qk_mix"].flops == 10 * rows * C
    assert by_type["mlp_router"].flops == \
        2 * rows * (D * HID + 2 * HID * HID + HID * EXPERTS) + 20 * rows * HID


def test_sites_are_counted_under_the_state_space_counter():
    def sites():
        f = default_registry().get("paddle_tpu_ssm_sites_total")
        return collections.Counter(
            {} if f is None else {k: c.value for k, c in f.samples()})

    t = _conv_inputs(5)
    env = {k: jnp.asarray(v) for k, v in t.items()}
    env["n"] = jnp.asarray([5, 5])
    before = sites()
    op = OpDesc("grouped_causal_conv1d",
                {"X": ["x"], "W": ["w"], "Bias": ["bias"], "Length": ["n"]},
                {"Out": ["o"], "State": ["s"]}, {"heads": HEADS})
    out = run_op(op, env, {"program": None})
    run_op(op, env, {})                            # shape inference: none
    run_op(OpDesc("grouped_conv_state_update",
                  {"State": ["s"], "X": ["x1"], "W": ["w"],
                   "Bias": ["bias"]},
                  {"Out": ["o"], "StateOut": ["s"]}, {"heads": HEADS}),
           dict(env, s=out["s"], x1=env["x"][:, :1]), {"program": None})
    assert dict(sites() - before) == {
        ("grouped_causal_conv1d", "composed", "0", str(HEADS)): 1,
        ("grouped_conv_state_update", "composed", "0", str(HEADS)): 1}
