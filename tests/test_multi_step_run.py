"""Executor.run(iterations=K): K training steps inside one compiled
program (lax.scan over the traced step) must match K separate run()
calls exactly — this is the mechanism that amortizes per-dispatch cost
over ms-scale bench steps.

Reference analog: repeated Executor.Run over a prepared context
(paddle/fluid/framework/executor.cc RunPreparedContext) — there the
loop lives in user code and pays per-call dispatch; here the loop is
compiled into the program.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def _build_train():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        label = layers.data("label", [1], dtype="float32")
        h = layers.fc(x, size=8, act="tanh")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, label))
        pt.optimizer.MomentumOptimizer(
            learning_rate=0.05, momentum=0.9).minimize(loss)
    return main, startup, loss


def _feed(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(16, 4).astype(np.float32)
    y = (x.sum(1, keepdims=True) > 2.0).astype(np.float32)
    x.flags.writeable = False
    y.flags.writeable = False
    return {"x": x, "label": y}


def test_iterations_matches_stepwise():
    K = 5
    feed = _feed()

    # K separate runs in a private scope
    scope_a = pt.core.scope.Scope()
    main, startup, loss = _build_train()
    exe = pt.Executor()
    exe.run(startup, scope=scope_a)
    loss_a = None
    for _ in range(K):
        (loss_a,) = exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope_a)

    # one scanned run in another scope, same init (re-run startup with
    # the same program so initializer seeds match)
    scope_b = pt.core.scope.Scope()
    exe.run(startup, scope=scope_b)
    (loss_b,) = exe.run(main, feed=feed, fetch_list=[loss],
                        scope=scope_b, iterations=K)

    np.testing.assert_allclose(loss_b, loss_a, rtol=1e-5, atol=1e-6)
    # every parameter and optimizer accumulator must agree
    for name in sorted(scope_a.local_names()):
        if name.startswith("@"):
            continue
        va, vb = np.asarray(scope_a.get(name)), np.asarray(
            scope_b.get(name))
        np.testing.assert_allclose(vb, va, rtol=1e-5, atol=1e-6,
                                   err_msg=name)


def test_iterations_advances_step_counter():
    from paddle_tpu.core.executor import STEP_VAR
    scope = pt.core.scope.Scope()
    main, startup, loss = _build_train()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    before = int(np.asarray(scope.get(STEP_VAR)))
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope,
            iterations=7)
    assert int(np.asarray(scope.get(STEP_VAR))) == before + 7


def test_iterations_or_reduces_while_flags(monkeypatch):
    """A bounded While truncated on an EARLY scan iteration (but clean
    on the final one) must still trip the exhaustion check: flags OR
    across iterations rather than reporting the last one."""
    import paddle_tpu.core.executor as ex_mod
    from paddle_tpu.layers import control_flow as cf

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        # trip target decreases 5 -> 2 -> -1 across outer steps: with
        # max_steps=3 only the FIRST outer iteration truncates
        target = layers.create_global_var([1], 5.0, "float32",
                                          persistable=True,
                                          name="trip_target")
        s = layers.fill_constant([1], "float32", 0.0)
        cond = cf.less_than_v(s, target)
        w = cf.While(cond, max_steps=3)
        with w.block():
            t = layers.elementwise_add(
                s, layers.fill_constant([1], "float32", 1.0))
            layers.assign(t, output=s)
            cf.less_than_v(s, target, cond=cond)
        newt = layers.elementwise_sub(
            target, layers.fill_constant([1], "float32", 3.0))
        layers.assign(newt, output=target)
    exe = pt.Executor()
    exe.run(startup)
    monkeypatch.setattr(ex_mod, "CHECK_WHILE_BOUND", True)
    with pytest.raises(RuntimeError, match="max_steps"):
        exe.run(main, fetch_list=[s], iterations=3)


def test_iterations_rejects_stateful_ops():
    from paddle_tpu.layers import csp
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ch = csp.make_channel("float32", capacity=4)
        x = layers.fill_constant([1], "float32", 1.0)
        csp.channel_send(ch, x)
        y = csp.channel_recv(ch, shape=[1], dtype="float32")
    exe = pt.Executor()
    with pytest.raises(RuntimeError, match="stateful"):
        exe.run(main, fetch_list=[y], iterations=2)
