"""The gated delta-rule ops (ops/delta_ops.py) against the recurrence as
it is written, one position at a time in float64: the chunked prefill at
lengths on, under and over a chunk's edge and on a ragged batch, with
and without an initial state; prefill then in-place updates against the
longer prefill; the Pallas update in interpret mode against the
composition; and what the shape inference, the cost model and the site
counter say of them. What the TPU's compiler makes of the kernel is
tests/test_tpu_compile.py's, what the chip runs chipbench's."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.ir import OpDesc
from paddle_tpu.core.registry import run_op
from paddle_tpu.observability import default_registry
from paddle_tpu.ops import delta_ops
from paddle_tpu.ops.pallas import delta_state_update as update_kernel

H, DK, DV = 4, 16, 32


def _inputs(s, seed=0, n=1):
    rng = np.random.default_rng(seed)
    return dict(
        q=rng.normal(0, 1, (n, s, H * DK)).astype(np.float32),
        k=rng.normal(0, 1, (n, s, H * DK)).astype(np.float32),
        v=rng.normal(0, 1, (n, s, H * DV)).astype(np.float32),
        a=rng.normal(0, 1, (n, s, H)).astype(np.float32),
        b=rng.normal(0, 1, (n, s, H)).astype(np.float32),
        a_log=np.log(rng.uniform(1, 16, H)).astype(np.float32),
        dt_bias=rng.normal(-3, 2, H).astype(np.float32))


def _softplus(v):
    return np.log1p(np.exp(-np.abs(v))) + np.maximum(v, 0)


def recurrence(t, length, initial=None, beta_scale=2.0):
    """The delta rule as written, in float64: (o [n, S, H * DV], state
    [n, DK, H * DV] after row length - 1)."""
    n, s, _ = t["q"].shape

    def unit(x):
        x = x.astype(np.float64).reshape(n, s, H, DK)
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q, k = unit(t["q"]) * DK ** -0.5, unit(t["k"])
    v = t["v"].astype(np.float64).reshape(n, s, H, DV)
    alpha = np.exp(-np.exp(t["a_log"].astype(np.float64))
                   * _softplus(t["a"].astype(np.float64) + t["dt_bias"]))
    beta = beta_scale / (1.0 + np.exp(-t["b"].astype(np.float64)))
    state = np.zeros((n, H, DK, DV)) if initial is None else \
        np.moveaxis(np.asarray(initial, np.float64)
                    .reshape(n, DK, H, DV), 2, 1).copy()
    o = np.zeros((n, s, H, DV))
    for i in range(n):
        for pos in range(min(s, int(length[i]))):
            for h in range(H):
                st = alpha[i, pos, h] * state[i, h]
                r = st.T @ k[i, pos, h]          # READ before the write
                d = beta[i, pos, h] * (v[i, pos, h] - r)
                st = st + np.outer(k[i, pos, h], d)
                state[i, h] = st
                o[i, pos, h] = st.T @ q[i, pos, h]
    return o.reshape(n, s, H * DV), \
        np.moveaxis(state, 1, 2).reshape(n, DK, H * DV)


SLOTS = {"Q": "q", "K": "k", "V": "v", "A": "a", "B": "b",
         "ALog": "a_log", "DtBias": "dt_bias"}


def _prefill_op(t, length, chunk, initial=None, extra=None, **attrs):
    inputs = {slot: [name] for slot, name in SLOTS.items()}
    inputs["Length"] = ["n"]
    env = {k: jnp.asarray(v) for k, v in t.items()}
    env["n"] = jnp.asarray(length, jnp.int64)
    if initial is not None:
        inputs["Initial"], env["s0"] = ["s0"], jnp.asarray(initial)
    op = OpDesc("gated_delta_prefill", inputs,
                {"Out": ["o"], "State": ["s"]},
                dict({"chunk": chunk}, **attrs))
    out = run_op(op, env, extra or {})
    return np.asarray(out["o"]), np.asarray(out["s"])


def _step_op(state, t, pos, extra=None, **attrs):
    inputs = {slot: [name] for slot, name in SLOTS.items()}
    inputs["State"] = ["s"]
    env = {k: jnp.asarray(v[:, pos:pos + 1] if v.ndim == 3 else v)
           for k, v in t.items()}
    env["s"] = jnp.asarray(state)
    op = OpDesc("gated_delta_state_update", inputs,
                {"Out": ["o"], "StateOut": ["s"]}, attrs)
    out = run_op(op, env, extra or {})
    return np.asarray(out["o"]), np.asarray(out["s"])


def _sites():
    fam = default_registry().get("paddle_tpu_delta_sites_total")
    if fam is None:
        return collections.Counter()
    return collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


# -- the chunked form ---------------------------------------------------

# the issue's lengths at the served chunk of 64 (one position; one short
# of a chunk; a chunk; one past its edge), then S a whole number of
# chunks, S not, one chunk longer than S, Length inside the first chunk
# and on a chunk's edge
@pytest.mark.parametrize("s,chunk,length", [
    (1, 64, 1), (63, 64, 63), (64, 64, 64), (65, 64, 65), (130, 64, 129),
    (16, 4, 16), (19, 4, 19), (19, 8, 11), (19, 8, 8), (7, 16, 5),
    (33, 8, 1)])
def test_the_chunked_prefill_is_the_recurrence_one_position_at_a_time(
        s, chunk, length):
    t = _inputs(s, seed=s + chunk)
    want_o, want_state = recurrence(t, [length])
    o, state = _prefill_op(t, [length], chunk)
    # float32 against float64: the order of sums, nothing else
    np.testing.assert_allclose(o[:, :length], want_o[:, :length],
                               rtol=1e-4, atol=2e-5)
    # rows at and beyond Length neither decay nor write
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=2e-5)


def test_rows_of_a_ragged_batch_stop_at_their_own_lengths():
    t = _inputs(70, seed=5, n=4)
    lengths = [70, 1, 64, 37]
    want_o, want_state = recurrence(t, lengths)
    o, state = _prefill_op(t, lengths, 64)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(o[row, :n], want_o[row, :n], rtol=1e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=2e-5)


def test_an_initial_state_is_where_the_rows_start_from():
    """A prompt in two halves, the second from the state the first left,
    is the prompt in one (what chunked prefill over a state will call:
    no second op)."""
    t = _inputs(50, seed=8, n=2)
    _, want_state = recurrence(t, [50, 50])
    first = {k: v[:, :23] if v.ndim == 3 else v for k, v in t.items()}
    second = {k: v[:, 23:] if v.ndim == 3 else v for k, v in t.items()}
    _, half = _prefill_op(first, [23, 23], 8)
    want_o, _ = recurrence(second, [27, 27], initial=half)
    o, state = _prefill_op(second, [27, 27], 8, initial=half)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=2e-5)


def test_beta_scale_is_the_negative_eigenvalues_factor():
    t = _inputs(12, seed=4)
    for scale in (1.0, 2.0):
        want_o, _ = recurrence(t, [12], beta_scale=scale)
        o, _ = _prefill_op(t, [12], 4, beta_scale=scale)
        np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=2e-5)
    assert np.abs(recurrence(t, [12], beta_scale=1.0)[0]
                  - want_o).max() > 1e-2


def test_bfloat16_inputs_give_float32_state_and_an_output_at_their_width():
    t = _inputs(20, seed=6)
    narrow = {k: jnp.asarray(v, jnp.bfloat16) if v.ndim == 3 else v
              for k, v in t.items()}
    o, state = _prefill_op(narrow, [20], 8)
    assert o.dtype == jnp.bfloat16 and state.dtype == np.float32
    # the rounded inputs, widened, through float64: what is left is
    # the output's own rounding (2^-9 relative)
    widened = {k: np.asarray(v.astype(jnp.float32)) if hasattr(v, "astype")
               and v.ndim == 3 else v for k, v in narrow.items()}
    want_o, want_state = recurrence(widened, [20])
    np.testing.assert_allclose(np.asarray(o, np.float32), want_o,
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=2e-5)


# -- one token a slot ----------------------------------------------------

@pytest.mark.parametrize("n_prompt,k_steps", [(1, 5), (6, 3), (64, 4)])
def test_prefill_then_updates_is_the_longer_prefill(n_prompt, k_steps):
    total = n_prompt + k_steps
    t = _inputs(total, seed=total, n=3)
    want_o, want_state = recurrence(t, [total] * 3)
    _, state = _prefill_op(t, [n_prompt] * 3, 64)
    for pos in range(n_prompt, total):
        o, state = _step_op(state, t, pos)
        np.testing.assert_allclose(o[:, 0], want_o[:, pos], rtol=1e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=2e-5)


def _kernel_operands(slots, heads, d_k, d_v, seed=0):
    rng = np.random.default_rng(seed)
    k = rng.normal(0, 1, (slots, heads, d_k))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return [jnp.asarray(x, jnp.float32) for x in (
        rng.normal(0, 1, (slots, d_k, heads * d_v)),
        rng.normal(0, 0.3, (slots, heads, d_k)), k,
        rng.normal(0, 1, (slots, heads * d_v)),
        rng.uniform(0.2, 1, (slots, heads)),
        rng.uniform(0, 2, (slots, heads)))]


# a group of four 32-wide heads in one lane word; the served widths (30
# heads of 96 x 192: blocks of 10 heads, groups of 2); six 64-wide heads
# (a block of 6); one 128-wide head a group, five a block of 5
@pytest.mark.parametrize("slots,heads,d_k,d_v", [
    (3, 4, 16, 32), (2, 30, 96, 192), (3, 6, 8, 64), (2, 5, 8, 128)])
def test_update_kernel_in_interpret_mode_is_the_composition(slots, heads,
                                                            d_k, d_v):
    state, q, k, v, alpha, beta = _kernel_operands(slots, heads, d_k, d_v,
                                                   seed=heads)
    assert update_kernel.fits(state.shape, state.dtype, heads)
    new, o = update_kernel.delta_state_update(state, q, k, v, alpha, beta,
                                              interpret=True)
    want, want_o = delta_ops.delta_step(
        state.reshape(slots, d_k, heads, d_v), q, k,
        v.reshape(slots, heads, d_v), alpha, beta)
    np.testing.assert_allclose(np.asarray(new),
                               np.asarray(want).reshape(state.shape),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(want_o).reshape(slots, -1),
                               rtol=1e-5, atol=2e-5)


def test_update_kernel_serves_only_what_it_can():
    fits = update_kernel.fits
    assert fits((40, 96, 5760), jnp.float32, 30)
    assert not fits((40, 96, 5760), jnp.bfloat16, 30)     # a rounded state
    assert not fits((40, 96, 30, 192), jnp.float32, 30)   # head-major
    assert not fits((40, 100, 5760), jnp.float32, 30)     # sublane tiles
    assert not fits((4, 16, 3 * 192), jnp.float32, 3)     # half a group
    assert not fits((4, 16, 100), jnp.float32, 3)
    assert update_kernel.group_heads(192) == 2
    assert update_kernel.block_heads(30, 192) == 10
    assert update_kernel.block_heads(4, 32) == 4
    assert update_kernel.block_heads(14, 192) == 2
    with pytest.raises(ValueError, match="cannot serve"):
        update_kernel.delta_state_update(
            jnp.zeros((2, 16, 100)), jnp.zeros((2, 3, 16)),
            jnp.zeros((2, 3, 16)), jnp.zeros((2, 100)), jnp.zeros((2, 3)),
            jnp.zeros((2, 3)))


def test_update_rule_takes_the_kernel_on_a_tpu_and_says_so(monkeypatch):
    """The choice is made on what the trace observes: steer the backend
    and the rule hands the state to the kernel (interpreted here)."""
    t = _inputs(3, seed=9, n=3)
    state = np.random.default_rng(1).normal(0, 1, (3, DK, H * DV)) \
        .astype(np.float32)
    composed = _step_op(state, t, 1, {"program": None})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(update_kernel, "_interpret_default", lambda: True)
    before = _sites()
    through_kernel = _step_op(state, t, 1, {"program": None})
    assert dict(_sites() - before) == {
        ("gated_delta_state_update", "kernel", "0"): 1}
    for ours, theirs in zip(through_kernel, composed):
        np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)
    # a mesh, or a state that is not float32, takes the composition
    before = _sites()
    _step_op(state, t, 1, {"program": None, "mesh": object()})
    _step_op(state.astype(jnp.bfloat16), t, 1, {"program": None})
    assert dict(_sites() - before) == {
        ("gated_delta_state_update", "composed", "0"): 2}
    # the build's shape inference carries no program: no site
    before = _sites()
    _step_op(state, t, 1, {})
    assert not _sites() - before


def test_sites_are_counted_by_op_path_and_chunk():
    t = _inputs(9, seed=2)
    before = _sites()
    _prefill_op(t, [9], 4, extra={"program": None})
    _prefill_op(t, [9], 64, extra={"program": None})
    _step_op(np.zeros((1, DK, H * DV), np.float32), t, 0,
             {"program": None})
    assert dict(_sites() - before) == {
        ("gated_delta_prefill", "chunked", "4"): 1,
        ("gated_delta_prefill", "chunked", "64"): 1,
        ("gated_delta_state_update", "composed", "0"): 1}


# -- what the build and the cost model read -----------------------------------

def _program_with_the_ops():
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        def data(name, shape, dtype="float32"):
            return layers.data(name, shape, dtype=dtype,
                               append_batch_size=False)
        q, k = data("q", [2, 12, H * DK], "bfloat16"), \
            data("k", [2, 12, H * DK], "bfloat16")
        v = data("v", [2, 12, H * DV], "bfloat16")
        a, b = data("a", [2, 12, H], "bfloat16"), \
            data("b", [2, 12, H], "bfloat16")
        vec = [data(n, [H]) for n in ("a_log", "dt_bias")]
        length = data("length", [2], "int64")
        o, state = layers.gated_delta_prefill(q, k, v, a, b, *vec, length,
                                              chunk=4)
        held = data("held", [2, DK, H * DV])
        q1, k1 = data("q1", [2, 1, H * DK], "bfloat16"), \
            data("k1", [2, 1, H * DK], "bfloat16")
        v1 = data("v1", [2, 1, H * DV], "bfloat16")
        a1, b1 = data("a1", [2, 1, H], "bfloat16"), \
            data("b1", [2, 1, H], "bfloat16")
        o1 = layers.gated_delta_state_update(held, q1, k1, v1, a1, b1,
                                             *vec)
    return main, dict(o=o, state=state, o1=o1, held=held)


@pytest.mark.parametrize("name,shape,dtype", [
    ("o", [2, 12, H * DV], "bfloat16"),
    ("state", [2, DK, H * DV], "float32"),
    ("o1", [2, 1, H * DV], "bfloat16"),
    ("held", [2, DK, H * DV], "float32")])
def test_shape_inference_gives_every_output_its_shape_and_width(
        name, shape, dtype):
    _, v = _program_with_the_ops()
    assert list(v[name].shape) == shape and v[name].dtype == dtype


def test_cost_model_books_the_chunked_form_and_the_update():
    from paddle_tpu.analysis import cost_model
    main, _ = _program_with_the_ops()
    cost = cost_model.program_cost(main, batch=1)
    by_type = {row.op_type: row for row in cost.ops}
    n, s, c = 2, 12, 4
    assert by_type["gated_delta_prefill"].flops == n * s * H * (
        4 * c * DK + c * (DV + DK) + 2 * c * DV + 6 * DK * DV)
    assert by_type["gated_delta_state_update"].flops == \
        7 * 2 * DK * H * DV
