"""The counted form of StaticRNN (``StaticRNN(steps=T)``): a sub-block
run T times on its memories alone, against a Python loop over the same
weights — the forward value, the stacked step outputs, the gradient of a
parameter the body reads as closure on every pass (ONE gradient
variable, the sum over the passes), T = 1, what misuse raises, and
what either form keeps of a pass for its transpose (its products'
outputs; the rest of the body is computed again) against the body traced
without ``jax.checkpoint``."""
import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.layers.control_flow import StaticRNN

D = 6


def _build(steps, lr=None):
    """h <- tanh(h W + b), `steps` times from the fed x; the loss is the
    mean of the stacked outputs times (step + 1)."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [D])
        rnn = StaticRNN(steps=steps)
        with rnn.step():
            h = rnn.memory(init=x)
            new = layers.fc(h, size=D, act="tanh", name="cell")
            rnn.update_memory(h, new)
            rnn.step_output(new)
        stacked = rnn()
        weights = layers.assign(np.arange(1, steps + 1, dtype=np.float32)
                                .reshape(steps, 1, 1))
        loss = layers.mean(layers.elementwise_mul(stacked, weights))
        if lr is not None:
            pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    return main, startup, stacked, loss


def _python_loop(x, w, b, steps):
    import jax.numpy as jnp
    outs, h = [], x
    for _ in range(steps):
        h = jnp.tanh(h @ w + b)
        outs.append(h)
    stacked = jnp.stack(outs)
    scale = jnp.arange(1, steps + 1, dtype=jnp.float32).reshape(-1, 1, 1)
    return jnp.mean(stacked * scale), stacked


@pytest.fixture
def fresh():
    pt.reset_default_programs()
    pt.reset_global_scope()
    yield
    pt.reset_global_scope()


def _params(main):
    scope = pt.global_scope()
    by = {p.name: np.asarray(scope.get(p.name))
          for p in main.all_parameters()}
    (w,) = [v for v in by.values() if v.ndim == 2]
    (b,) = [v for v in by.values() if v.ndim == 1]
    return w, b


@pytest.mark.parametrize("steps", [1, 4])
def test_forward_and_stacked_outputs_match_a_python_loop(fresh, steps):
    main, startup, stacked, loss = _build(steps)
    exe = pt.Executor()
    exe.run(startup)
    x = np.random.RandomState(0).randn(3, D).astype(np.float32)
    got_loss, got = exe.run(main, feed={"x": x},
                            fetch_list=[loss, stacked])
    want_loss, want = _python_loop(x, *_params(main), steps)
    assert got.shape == (steps, 3, D)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    exe.close()


@pytest.mark.parametrize("steps", [1, 4])
def test_a_closure_parameter_gets_one_summed_gradient(fresh, steps):
    lr = 0.5
    main, startup, _stacked, loss = _build(steps, lr=lr)
    # one loop op, one grad op of it, and one gradient variable a
    # parameter: the sum over the passes is made inside the scan
    ops = main.desc.global_block.ops
    assert [o.type for o in ops].count("static_rnn") == 1
    (gop,) = [o for o in ops if o.type == "__vjp__"
              and o.attrs["fwd_op"]["type"] == "static_rnn"]
    names = [p.name for p in main.all_parameters()]
    assert sorted(gop.attrs["closure_names"]) == sorted(names)
    written = [n for o in ops for n in o.output_names()]
    for n in names:
        assert written.count(n + "@GRAD") == 1
        assert not [w for w in written if w.startswith(n + "@GRAD@")]
    exe = pt.Executor()
    exe.run(startup)
    w0, b0 = _params(main)
    x = np.random.RandomState(1).randn(3, D).astype(np.float32)
    exe.run(main, feed={"x": x}, fetch_list=[loss])
    w1, b1 = _params(main)
    gw, gb = jax.grad(lambda w, b: _python_loop(x, w, b, steps)[0],
                      argnums=(0, 1))(w0, b0)
    np.testing.assert_allclose((w0 - w1) / lr, gw, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose((b0 - b1) / lr, gb, rtol=1e-4, atol=1e-6)
    exe.close()


def test_the_sub_block_and_the_scan_body_do_not_depend_on_the_count(fresh):
    sizes = {}
    for steps in (1, 4):
        pt.reset_default_programs()
        pt.reset_global_scope()
        main, _startup, _stacked, _loss = _build(steps)
        (op,) = [o for o in main.desc.global_block.ops
                 if o.type == "static_rnn"]
        assert op.attrs["steps"] == steps and not op.input("X")
        sizes[steps] = len(main.desc.blocks[op.attrs["sub_block_idx"]].ops)
        out = main.desc.global_block.find_var_recursive(
            op.output("Out")[0])
        assert out.shape[0] == steps
    assert sizes[1] == sizes[4]


def test_the_loop_site_is_counted_with_its_passes(fresh):
    from paddle_tpu.observability.registry import default_registry

    def count():
        fam = default_registry().get("paddle_tpu_loop_sites_total")
        return sum(child.value for labels, child in fam.samples()
                   if labels[0] == "3") if fam else 0

    before = count()
    main, startup, _stacked, loss = _build(3)
    exe = pt.Executor()
    exe.run(startup)
    exe.run(main, feed={"x": np.zeros((2, D), np.float32)},
            fetch_list=[loss])
    exe.close()
    assert count() == before + 1


def test_the_loop_site_says_what_it_keeps(fresh):
    from paddle_tpu.observability.registry import default_registry
    main, startup, _stacked, loss = _build(5, lr=0.1)
    exe = pt.Executor()
    exe.run(startup)
    exe.run(main, feed={"x": np.zeros((2, D), np.float32)},
            fetch_list=[loss])
    exe.close()
    fam = default_registry().get("paddle_tpu_loop_sites_total")
    assert fam.labelnames == ("passes", "body_ops", "keeps")
    sub_ops = len(main.desc.blocks[1].ops)
    (site,) = [labels for labels, _child in fam.samples()
               if labels[0] == "5"]
    assert site == ("5", str(sub_ops), "products_and_kernels")


T_IN = 5


def _stepped(product, lr=0.5):
    """The step-input form: h <- tanh(x_t W + h U + b) with `product`,
    h <- tanh(x_t * w + h * u) (no product in the body) without."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [T_IN, 3, D], append_batch_size=False)
        h0 = layers.data("h0", [3, D], append_batch_size=False)
        rnn = StaticRNN()
        with rnn.step():
            word = rnn.step_input(x)
            prev = rnn.memory(init=h0)
            if product:
                new = layers.tanh(layers.elementwise_add(
                    layers.fc(word, size=D, bias_attr=False),
                    layers.fc(prev, size=D)))
            else:
                w, u = (layers.create_parameter(
                    [D], "float32",
                    default_initializer=pt.initializer.UniformInitializer(
                        0.5, 1.5, seed=seed)) for seed in (1, 2))
                new = layers.tanh(layers.elementwise_add(
                    layers.elementwise_mul(word, w),
                    layers.elementwise_mul(prev, u)))
            rnn.update_memory(prev, new)
            rnn.step_output(new)
        loss = layers.mean(layers.square(rnn()))
        pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    return main, startup, loss


def _run(main, startup, loss, feed):
    """(loss, every parameter's gradient, arrays a step the forward scan
    stacks) of one step."""
    from paddle_tpu.core.registry import grad_var_name
    from test_looped_lm import _scans
    names = [p.name for p in main.all_parameters()]
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    step = exe._compile(main.desc, main.desc.block(0), None, [loss.name],
                        scope)
    state = [{n: scope.get(n) for n in ns}
             for ns in (step.ro_names, step.rw_names)]
    fwd, bwd = _scans(jax.make_jaxpr(step.jitted)(
        feed, *state, np.int32(0)).jaxpr)
    assert not fwd.params["reverse"] and bwd.params["reverse"]
    got = exe.run(main, feed=feed,
                  fetch_list=[loss] + [grad_var_name(n) for n in names])
    exe.close()
    return got[0], got[1:], len(fwd.outvars) - fwd.params["num_carry"]


@pytest.mark.parametrize("form", ["counted", "stepped_product",
                                  "stepped_no_product"])
def test_loss_and_gradients_are_the_unwrapped_bodys(monkeypatch, form):
    """Bit-equal loss, gradients to f32 rounding, and what the scan
    stacks: the step output and the carry, the two products' outputs
    where the body has them, nothing of tanh or the adds."""
    from test_looped_lm import _unwrapped
    rng = np.random.RandomState(3)
    if form == "counted":
        feed = {"x": rng.randn(3, D).astype(np.float32)}
    else:
        feed = {"x": rng.randn(T_IN, 3, D).astype(np.float32),
                "h0": rng.randn(3, D).astype(np.float32)}
    got = {}
    for wrapped in (True, False):
        pt.reset_default_programs()
        pt.reset_global_scope()
        with monkeypatch.context() as m:
            if not wrapped:
                _unwrapped(m)
            if form == "counted":
                main, startup, _stacked, loss = _build(4, lr=0.5)
            else:
                main, startup, loss = _stepped(form == "stepped_product")
            got[wrapped] = _run(main, startup, loss, feed)
    pt.reset_global_scope()
    (loss, grads, kept), (want_loss, want, before) = got[True], got[False]
    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    assert len(grads) == len(want) == (3 if form == "stepped_product"
                                       else 2)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)
    products = {"counted": 1, "stepped_product": 2,
                "stepped_no_product": 0}[form]
    # the step output and the carry a pass, beside the products' outputs
    # (a step's input the transpose reads from the scan's own argument)
    assert kept == 2 + products <= before


def test_a_memory_keeps_its_dtype_across_steps(fresh):
    """A body that hands the carry back at another width (what AMP does
    to a float32 stream) must not change the scan's carry type."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [D])
        rnn = StaticRNN(steps=3)
        with rnn.step():
            h = rnn.memory(init=x)
            half = layers.cast(layers.scale(h, scale=2.0), "bfloat16")
            rnn.update_memory(h, half)
            rnn.step_output(half)
        out = rnn()
        total = layers.reduce_sum(layers.cast(out, "float32"))
    exe = pt.Executor()
    exe.run(startup)
    (got,) = exe.run(main, feed={"x": np.ones((2, D), np.float32)},
                     fetch_list=[total])
    assert float(got) == 2 * D * (2 + 4 + 8)
    exe.close()


def test_misuse_raises():
    with pytest.raises(ValueError, match="at least 1"):
        StaticRNN(steps=0)
    with pytest.raises(ValueError, match="whole number"):
        StaticRNN(steps=2.0)
    pt.reset_default_programs()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [D])
        seq = layers.data("seq", [5, D])
        rnn = StaticRNN(steps=2)
        with pytest.raises(ValueError, match="second length"):
            with rnn.step():
                rnn.step_input(seq)
        bare = StaticRNN()
        with pytest.raises(ValueError, match="takes its length"):
            with bare.step():
                h = bare.memory(init=x)
                bare.update_memory(h, layers.scale(h, scale=2.0))
        stale = StaticRNN(steps=2)
        with pytest.raises(ValueError, match="update_memory"):
            with stale.step():
                stale.memory(init=x)
