"""The state-space ops (ops/ssm_ops.py) against the recurrence as it is
written and against its quadratic form, the two sides of the causal
convolution, the in-place state updates against a longer prefill, the
Pallas state update in interpret mode against the composition, cached
attention at grouped key heads, and what the cost model, the shape
inference and the counters say of them. What the TPU's compiler makes
of the kernels and what the chip runs is chipbench's."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.ir import OpDesc
from paddle_tpu.core.registry import run_op
from paddle_tpu.observability import default_registry
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.pallas import decode_attention as attn_kernel
from paddle_tpu.ops.pallas import ssm_state_update as update_kernel

H, P, N, TAPS = 4, 8, 16, 4


def _inputs(s, seed=0, n=1):
    rng = np.random.default_rng(seed)
    return dict(
        x=rng.normal(0, 1, (n, s, H * P)).astype(np.float32),
        dt=rng.normal(0, 1, (n, s, H)).astype(np.float32),
        b=rng.normal(0, 1, (n, s, N)).astype(np.float32),
        c=rng.normal(0, 1, (n, s, N)).astype(np.float32),
        a_log=np.log(rng.uniform(1, 16, H)).astype(np.float32),
        dt_bias=rng.normal(0, 1, H).astype(np.float32),
        d=rng.normal(0, 1, H).astype(np.float32))


def _softplus(v):
    return np.log1p(np.exp(-np.abs(v))) + np.maximum(v, 0)


def recurrence(t, length):
    """The recurrence as written, one position at a time, in float64:
    (y [n, S, H * P], state [n, N, H * P] after row length - 1)."""
    n, s, _ = t["x"].shape
    x = t["x"].astype(np.float64).reshape(n, s, H, P)
    dt = _softplus(t["dt"].astype(np.float64) + t["dt_bias"])
    a = -np.exp(t["a_log"].astype(np.float64))
    state = np.zeros((n, N, H, P))
    y = np.zeros((n, s, H, P))
    for i in range(n):
        for pos in range(min(s, int(length[i]))):
            decay = np.exp(dt[i, pos] * a)                    # [H]
            state[i] = state[i] * decay[None, :, None] + np.einsum(
                "n,hp->nhp", t["b"][i, pos], dt[i, pos][:, None] * x[i, pos])
            y[i, pos] = np.einsum("nhp,n->hp", state[i], t["c"][i, pos]) \
                + t["d"][:, None] * x[i, pos]
    return y.reshape(n, s, H * P), state.reshape(n, N, H * P)


def quadratic(t):
    """Y = (L * C B^T) X + D x: every pair of rows at once."""
    n, s, _ = t["x"].shape
    x = t["x"].astype(np.float64).reshape(n, s, H, P)
    dt = _softplus(t["dt"].astype(np.float64) + t["dt_bias"])
    cum = np.cumsum(dt * -np.exp(t["a_log"].astype(np.float64)), axis=1)
    seen = np.tril(np.ones((s, s), bool))
    decay = np.where(seen[None, :, :, None],
                     np.exp(np.where(seen[None, :, :, None],
                                     cum[:, :, None] - cum[:, None], 0)),
                     0.0)                                     # [n,i,j,H]
    scores = np.einsum("zin,zjn->zij", t["c"], t["b"])
    weights = scores[..., None] * decay * dt[:, None]
    y = np.einsum("zijh,zjhp->zihp", weights, x) + t["d"][:, None] * x
    return y.reshape(n, s, H * P)


def _prefill_op(t, length, chunk, extra=None):
    op = OpDesc("ssd_prefill",
                {"X": ["x"], "Dt": ["dt"], "B": ["b"], "C": ["c"],
                 "ALog": ["a_log"], "DtBias": ["dt_bias"], "D": ["d"],
                 "Length": ["n"]},
                {"Y": ["y"], "State": ["s"]}, {"chunk": chunk})
    env = {k: jnp.asarray(v) for k, v in t.items()}
    env["n"] = jnp.asarray(length, jnp.int64)
    out = run_op(op, env, extra or {})
    return np.asarray(out["y"]), np.asarray(out["s"])


def _sites():
    fam = default_registry().get("paddle_tpu_ssm_sites_total")
    if fam is None:
        return collections.Counter()
    return collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


# -- the chunked scan ---------------------------------------------------

# S a whole number of chunks, S not (the last chunk is padded), one
# chunk longer than S, Length inside the first chunk, on a chunk's
# edge, and the whole sequence
@pytest.mark.parametrize("s,chunk,length", [
    (16, 4, 16), (19, 4, 19), (19, 8, 11), (19, 8, 8), (7, 16, 5),
    (33, 8, 1), (24, 8, 17)])
def test_ssd_prefill_is_the_recurrence_one_position_at_a_time(s, chunk,
                                                              length):
    t = _inputs(s, seed=s + chunk)
    want_y, want_state = recurrence(t, [length])
    y, state = _prefill_op(t, [length], chunk)
    np.testing.assert_allclose(y[:, :length], want_y[:, :length],
                               rtol=2e-4, atol=2e-4)
    # rows at and beyond Length neither decay nor feed the state
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s,chunk", [(12, 4), (21, 8), (9, 16)])
def test_ssd_prefill_is_the_quadratic_form(s, chunk):
    t = _inputs(s, seed=3 * s, n=2)
    y, _ = _prefill_op(t, [s, s], chunk)
    np.testing.assert_allclose(y, quadratic(t), rtol=2e-4, atol=2e-4)


def test_ssd_prefill_rows_of_a_batch_stop_at_their_own_lengths():
    t = _inputs(13, seed=5, n=3)
    lengths = [13, 4, 9]
    want_y, want_state = recurrence(t, lengths)
    y, state = _prefill_op(t, lengths, 4)
    for row, length in enumerate(lengths):
        np.testing.assert_allclose(y[row, :length], want_y[row, :length],
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


def test_ssd_prefill_multiplies_bfloat16_operands_and_keeps_f32_state():
    t = _inputs(24, seed=1)
    want_y, want_state = recurrence(t, [24])
    t16 = dict(t, **{k: jnp.asarray(t[k], jnp.bfloat16)
                     for k in ("x", "dt", "b", "c")})
    y, state = _prefill_op(t16, [24], 8)
    assert y.dtype.name == "bfloat16" and state.dtype == np.float32
    scale = np.abs(want_y).max()
    assert np.abs(y.astype(np.float32) - want_y).max() < 0.05 * scale
    assert np.abs(state - want_state).max() < 0.05 * np.abs(want_state).max()


# -- one token a slot, in place -----------------------------------------

def _update_op(state, t, pos, extra=None):
    op = OpDesc("ssm_state_update",
                {"State": ["s"], "X": ["x"], "Dt": ["dt"], "B": ["b"],
                 "C": ["c"], "ALog": ["a_log"], "DtBias": ["dt_bias"],
                 "D": ["d"]},
                {"Y": ["y"], "StateOut": ["s"]}, {})
    env = {k: jnp.asarray(v[:, pos:pos + 1]) for k, v in t.items()
           if k in ("x", "dt", "b", "c")}
    env.update({k: jnp.asarray(t[k]) for k in ("a_log", "dt_bias", "d")})
    env["s"] = jnp.asarray(state)
    out = run_op(op, env, extra or {})
    return np.asarray(out["y"]), np.asarray(out["s"])


@pytest.mark.parametrize("n_prompt,k_steps", [(5, 1), (8, 6), (3, 11)])
def test_prefill_then_updates_is_the_longer_prefill(n_prompt, k_steps):
    total = n_prompt + k_steps
    t = _inputs(total, seed=total, n=2)
    want_y, want_state = _prefill_op(t, [total, total], 4)
    _, state = _prefill_op(t, [n_prompt, n_prompt], 4)
    for pos in range(n_prompt, total):
        y, state = _update_op(state, t, pos)
        np.testing.assert_allclose(y[:, 0], want_y[:, pos],
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("slots,columns", [(3, 128), (5, 4096)])
def test_update_kernel_in_interpret_mode_is_the_composition(slots, columns):
    rng = np.random.default_rng(slots)
    state = rng.normal(0, 1, (slots, N, columns)).astype(np.float32)
    decay = rng.uniform(0, 1, (slots, columns)).astype(np.float32)
    dx = rng.normal(0, 1, (slots, columns)).astype(np.float32)
    b = rng.normal(0, 1, (slots, N)).astype(np.float32)
    c = rng.normal(0, 1, (slots, N)).astype(np.float32)
    assert update_kernel.fits(state.shape, state.dtype)
    new, y = update_kernel.ssm_state_update(
        *(jnp.asarray(a) for a in (state, decay, dx, b, c)),
        interpret=True)
    want = state * decay[:, None, :] + b[:, :, None] * dx[:, None, :]
    np.testing.assert_allclose(np.asarray(new), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y),
                               np.einsum("snl,sn->sl", want, c),
                               rtol=1e-5, atol=1e-5)


def test_update_kernel_serves_only_what_it_can():
    assert update_kernel.fits((64, 128, 4096), jnp.float32)
    assert not update_kernel.fits((64, 128, 4096), jnp.bfloat16)
    assert not update_kernel.fits((64, 128, 100), jnp.float32)
    assert not update_kernel.fits((64, 64, 64, 128), jnp.float32)
    assert update_kernel.lane_block(4096) == 2048
    assert update_kernel.lane_block(384) == 128
    with pytest.raises(ValueError, match="cannot serve"):
        update_kernel.ssm_state_update(
            jnp.zeros((2, 16, 100)), jnp.zeros((2, 100)),
            jnp.zeros((2, 100)), jnp.zeros((2, 16)), jnp.zeros((2, 16)))


def test_update_rule_takes_the_kernel_on_a_tpu_and_says_so(monkeypatch):
    """The choice is made on what the trace observes: steer the backend
    and the rule hands the state to the kernel (interpreted here)."""
    rng = np.random.default_rng(9)
    heads, width, slots = 4, 32, 3            # 128 columns: a lane block
    env = dict(
        s=rng.normal(0, 1, (slots, N, heads * width)),
        x=rng.normal(0, 1, (slots, 1, heads * width)),
        dt=rng.normal(0, 1, (slots, 1, heads)),
        b=rng.normal(0, 1, (slots, 1, N)), c=rng.normal(0, 1, (slots, 1, N)),
        a_log=np.log(rng.uniform(1, 16, heads)),
        dt_bias=rng.normal(0, 1, heads), d=rng.normal(0, 1, heads))
    env = {k: jnp.asarray(v, jnp.float32) for k, v in env.items()}
    op = OpDesc("ssm_state_update",
                {"State": ["s"], "X": ["x"], "Dt": ["dt"], "B": ["b"],
                 "C": ["c"], "ALog": ["a_log"], "DtBias": ["dt_bias"],
                 "D": ["d"]},
                {"Y": ["y"], "StateOut": ["s"]}, {})
    composed = run_op(op, dict(env), {"program": None})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(update_kernel, "_interpret_default", lambda: True)
    before = _sites()
    through_kernel = run_op(op, dict(env), {"program": None})
    assert dict(_sites() - before) == {
        ("ssm_state_update", "kernel", "0", str(heads)): 1}
    for name in ("y", "s"):
        np.testing.assert_allclose(np.asarray(through_kernel[name]),
                                   np.asarray(composed[name]),
                                   rtol=1e-5, atol=1e-5)
    # the build's shape inference carries no program: no kernel traced
    before = _sites()
    run_op(op, dict(env), {})
    assert not _sites() - before


def test_sites_are_counted_by_op_path_chunk_and_group():
    t = _inputs(9, seed=2)
    before = _sites()
    _prefill_op(t, [9], 4, {"program": None})
    _, state = _prefill_op(t, [4], 4)              # shape inference: none
    _update_op(state, t, 4, {"program": None})
    assert dict(_sites() - before) == {
        ("ssd_prefill", "chunked", "4", str(H)): 1,
        ("ssm_state_update", "composed", "0", str(H)): 1}


# -- the convolution ------------------------------------------------------

def _conv_inputs(s, ch=12, seed=0, n=2):
    rng = np.random.default_rng(seed)
    return dict(x=rng.normal(0, 1, (n, s, ch)).astype(np.float32),
                w=rng.normal(0, 1, (TAPS, ch)).astype(np.float32),
                bias=rng.normal(0, 1, ch).astype(np.float32))


def _conv_op(t, length):
    op = OpDesc("causal_conv1d",
                {"X": ["x"], "W": ["w"], "Bias": ["bias"], "Length": ["n"]},
                {"Out": ["o"], "State": ["s"]}, {})
    env = {k: jnp.asarray(v) for k, v in t.items()}
    env["n"] = jnp.asarray(length, jnp.int64)
    out = run_op(op, env, {})
    return np.asarray(out["o"]), np.asarray(out["s"])


def _conv_step(state, t, pos):
    op = OpDesc("conv_state_update",
                {"State": ["s"], "X": ["x"], "W": ["w"], "Bias": ["bias"]},
                {"Out": ["o"], "StateOut": ["s"]}, {})
    out = run_op(op, {"s": jnp.asarray(state),
                      "x": jnp.asarray(t["x"][:, pos:pos + 1]),
                      "w": jnp.asarray(t["w"]),
                      "bias": jnp.asarray(t["bias"])}, {})
    return np.asarray(out["o"]), np.asarray(out["s"])


@pytest.mark.parametrize("s,lengths", [(10, [10, 6]), (5, [2, 1]),
                                       (8, [3, 8]), (4, [4, 4])])
def test_causal_conv1d_and_its_window(s, lengths):
    t = _conv_inputs(s, seed=s)
    out, window = _conv_op(t, lengths)
    n, _, ch = t["x"].shape
    for row in range(n):
        for pos in range(s):
            want = t["bias"].copy()
            for k in range(TAPS):          # tap k reads TAPS-1-k back
                src = pos - (TAPS - 1 - k)
                if src >= 0:
                    want = want + t["w"][k] * t["x"][row, src]
            np.testing.assert_allclose(out[row, pos], want, rtol=1e-5,
                                       atol=1e-5)
        # the last TAPS - 1 REAL inputs, oldest first, zeros before 0
        real = np.zeros((TAPS - 1, ch), np.float32)
        for k in range(TAPS - 1):
            src = lengths[row] - (TAPS - 1) + k
            if src >= 0:
                real[k] = t["x"][row, src]
        np.testing.assert_array_equal(window[row].reshape(TAPS - 1, ch),
                                      real)


@pytest.mark.parametrize("n_prompt,k_steps", [(1, 5), (6, 3), (2, 2)])
def test_conv_prefill_then_updates_is_the_longer_prefill(n_prompt, k_steps):
    total = n_prompt + k_steps
    t = _conv_inputs(total, seed=total)
    want_out, want_window = _conv_op(t, [total, total])
    _, window = _conv_op(t, [n_prompt, n_prompt])
    for pos in range(n_prompt, total):
        out, window = _conv_step(window, t, pos)
        np.testing.assert_allclose(out[:, 0], want_out[:, pos], rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_array_equal(window, want_window)


def test_slot_state_write_overwrites_the_whole_slot_and_no_other():
    state = jnp.ones((3, 4, 5))
    new = jnp.full((1, 4, 5), 7.0)
    op = OpDesc("slot_state_write",
                {"State": ["s"], "New": ["new"], "Slot": ["slot"]},
                {"StateOut": ["s"]}, {})
    out = np.asarray(run_op(op, {"s": state, "new": new,
                                 "slot": jnp.asarray([1])}, {})["s"])
    assert (out[1] == 7).all() and (out[[0, 2]] == 1).all()


# -- cached attention at grouped key heads -----------------------------------

def _kvlen_rule(q, k, v, kv_len, bound):
    op = OpDesc("scaled_dot_product_attention",
                {"Q": ["q"], "K": ["k"], "V": ["v"], "KvLen": ["n"]},
                {"Out": ["o"]}, {"causal": False, "kv_bound": bound})
    return run_op(op, {"q": q, "k": k, "v": v, "n": kv_len},
                  {"program": None})["o"]


def _grouped_operands(group, dtype, slots=6, key_heads=2, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(slots, group * key_heads, 1, 64), jnp.float32)
    k = jnp.asarray(rng.randn(slots, key_heads, 512, 64), dtype)
    v = jnp.asarray(rng.randn(slots, key_heads, 512, 64), dtype)
    return q, k, v


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kvlen_at_grouped_key_heads_kernel_against_composition(
        group, dtype, monkeypatch):
    """Query head h * group + g reads key head h, on both paths."""
    q, k, v = _grouped_operands(group, dtype, seed=group)
    kv_len = jnp.asarray([1, 255, 256, 257, 0, 300], jnp.int64)
    composed = np.asarray(_kvlen_rule(q, k, v, kv_len, 512))
    # by hand, one query head at a time
    kf, vf = np.asarray(k, np.float32), np.asarray(v, np.float32)
    for s, n in enumerate(np.asarray(kv_len)):
        for h in range(q.shape[1]):
            if n == 0:
                continue
            kh = h // group
            scores = kf[s, kh, :n] @ np.asarray(q)[s, h, 0] / 8.0
            p = np.exp(scores - scores.max())
            want = (p / p.sum()) @ vf[s, kh, :n]
            np.testing.assert_allclose(composed[s, h, 0], want, rtol=2e-4,
                                       atol=2e-4)
    monkeypatch.setattr(nn_ops, "_decode_kernel_lane_axis",
                        lambda *a: 2)
    fam = default_registry().get("paddle_tpu_sdpa_sites_total")
    before = {k_: c.value for k_, c in fam.samples()} if fam else {}
    through_kernel = np.asarray(_kvlen_rule(q, k, v, kv_len, 512))
    fam = default_registry().get("paddle_tpu_sdpa_sites_total")
    after = {k_: c.value for k_, c in fam.samples()}
    label = ("decode_kernel", "kv_len", "0", "0", str(group), "bhsd")
    assert after.get(label, 0) - before.get(label, 0) == 1
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(through_kernel[live], composed[live],
                               rtol=2e-5, atol=2e-5)
    assert (through_kernel[~live] == 0).all()


def test_decode_attention_refuses_what_it_cannot_serve():
    q, k, v = _grouped_operands(4, jnp.float32)
    with pytest.raises(ValueError, match="cannot serve"):
        attn_kernel.decode_attention(q, k, v, jnp.zeros(6, jnp.int32),
                                     bound=100)


def test_kvlen_still_refuses_a_mask_and_causality():
    q, k, v = _grouped_operands(4, jnp.float32)
    op = OpDesc("scaled_dot_product_attention",
                {"Q": ["q"], "K": ["k"], "V": ["v"], "KvLen": ["n"]},
                {"Out": ["o"]}, {"causal": True, "kv_bound": 256})
    with pytest.raises(ValueError, match="KvLen"):
        run_op(op, {"q": q, "k": k, "v": v,
                    "n": jnp.zeros(6, jnp.int32)}, {})


# -- what the build and the cost model read -----------------------------------

def _program_with_the_ops():
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        def data(name, shape, dtype="float32"):
            return layers.data(name, shape, dtype=dtype,
                               append_batch_size=False)
        x = data("x", [2, 12, H * P], "bfloat16")
        dt = data("dt", [2, 12, H], "bfloat16")
        b, c = data("b", [2, 12, N], "bfloat16"), data("c", [2, 12, N],
                                                       "bfloat16")
        vec = [data(n, [H]) for n in ("a_log", "dt_bias", "d")]
        length = data("length", [2], "int64")
        y, state = layers.ssd_prefill(x, dt, b, c, *vec, length, chunk=4)
        w, bias = data("w", [TAPS, H * P], "bfloat16"), \
            data("bias", [H * P], "bfloat16")
        out, window = layers.causal_conv1d(x, w, bias, length)
        held = data("held", [2, N, H * P])
        x1, dt1 = data("x1", [2, 1, H * P], "bfloat16"), \
            data("dt1", [2, 1, H], "bfloat16")
        b1, c1 = data("b1", [2, 1, N], "bfloat16"), \
            data("c1", [2, 1, N], "bfloat16")
        y1 = layers.ssm_state_update(held, x1, dt1, b1, c1, *vec)
        held_w = data("held_w", [2, (TAPS - 1) * H * P], "bfloat16")
        o1 = layers.conv_state_update(held_w, x1, w, bias)
    return main, dict(y=y, state=state, out=out, window=window, y1=y1,
                      o1=o1, held=held, held_w=held_w)


@pytest.mark.parametrize("name,shape,dtype", [
    ("y", [2, 12, H * P], "bfloat16"),
    ("state", [2, N, H * P], "float32"),
    ("out", [2, 12, H * P], "bfloat16"),
    ("window", [2, (TAPS - 1) * H * P], "bfloat16"),
    ("y1", [2, 1, H * P], "bfloat16"),
    ("o1", [2, 1, H * P], "bfloat16"),
    ("held", [2, N, H * P], "float32"),
    ("held_w", [2, (TAPS - 1) * H * P], "bfloat16")])
def test_shape_inference_gives_every_output_its_shape_and_width(
        name, shape, dtype):
    _, v = _program_with_the_ops()
    assert list(v[name].shape) == shape and v[name].dtype == dtype


def test_cost_model_books_the_scan_the_update_and_the_taps():
    from paddle_tpu.analysis import cost_model
    main, _ = _program_with_the_ops()
    cost = cost_model.program_cost(main, batch=1)
    by_type = {}
    for row in cost.ops:
        by_type[row.op_type] = row
    s, q, cols = 12, 4, H * P
    assert by_type["ssd_prefill"].flops == \
        2 * 2 * s * (q * N + q * cols + 2 * N * cols)
    assert by_type["ssm_state_update"].flops == 5 * 2 * N * cols
    assert by_type["causal_conv1d"].flops == 2 * TAPS * 2 * s * cols
    assert by_type["conv_state_update"].flops == 2 * TAPS * 2 * cols
