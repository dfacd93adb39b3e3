"""Unbounded While gradients via the executor's probe-and-replay
WhileGrad (core/executor.py _probe_while_bounds + the dynamic_bound
masked-scan lowering in ops/control_flow_ops.py).

Reference capability: WhileGrad runs the backward over recorded
per-iteration step scopes for loops whose trip count is data-dependent
and unknown at trace time (while_op.cc:96-109). TPU-native form: a
forward probe measures the trip count, the program recompiles with the
bucketed bound baked into a differentiable masked scan.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.layers import control_flow as cf


def _build(lr=0.05, x0=0.3, target=2.0):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.create_parameter(
            shape=[1], dtype="float32", name="xparam",
            default_initializer=pt.initializer.ConstantInitializer(x0))
        thr = layers.data("thr", [1], dtype="float32")
        s = layers.fill_constant([1], "float32", 0.0)
        s.stop_gradient = False   # the loop carry is on the grad path
        cond = cf.less_than_v(s, thr)
        w = cf.While(cond)               # NO max_steps: trip count is
        with w.block():                  # data-dependent on the feed
            t = layers.elementwise_add(s, x)
            layers.assign(t, output=s)
            cf.less_than_v(s, thr, cond=cond)
        tgt = layers.fill_constant([1], "float32", target)
        loss = layers.reduce_sum(layers.square(layers.elementwise_sub(
            s, tgt)))
        pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    return main, startup, {"x": x, "s": s, "loss": loss, "w": w}


def _numpy_loop(x, thr, target):
    """Replicates the loop on the host for finite differences."""
    s, n = 0.0, 0
    while s < thr:
        s += x
        n += 1
    return (s - target) ** 2, n


def test_unbounded_while_gradient_matches_finite_differences():
    lr, x0, target = 0.05, 0.3, 2.0
    main, startup, f = _build(lr, x0, target)
    exe = pt.Executor()
    exe.run(startup)

    thr = np.asarray([1.0], np.float32)
    lv, steps = exe.run(main, feed={"thr": thr},
                        fetch_list=[f["loss"], f["w"].steps])
    # x=0.3, thr=1.0 -> s walks 0.3,0.6,0.9,1.2: four iterations
    assert int(np.asarray(steps)) == 4
    np.testing.assert_allclose(float(np.asarray(lv)),
                               (1.2 - target) ** 2, rtol=1e-5)

    # gradient applied by SGD == (x0 - x1)/lr; compare to central
    # finite differences of the host replica (eps small enough not to
    # cross a trip-count boundary)
    x1 = float(np.asarray(pt.global_scope().get("xparam")).reshape(()))
    g_applied = (x0 - x1) / lr
    eps = 1e-3
    fp, np_ = _numpy_loop(x0 + eps, 1.0, target)
    fm, nm = _numpy_loop(x0 - eps, 1.0, target)
    assert np_ == nm == 4
    g_fd = (fp - fm) / (2 * eps)
    np.testing.assert_allclose(g_applied, g_fd, rtol=1e-3)
    # analytic: dloss/dx = 2*(s-target)*n
    np.testing.assert_allclose(g_applied, 2 * (1.2 - target) * 4,
                               rtol=1e-4)


def test_unbounded_while_grad_recompiles_per_trip_count_bucket():
    lr, x0, target = 0.0, 0.3, 2.0   # lr=0 keeps the param frozen
    main, startup, f = _build(lr, x0, target)
    exe = pt.Executor()
    exe.run(startup)

    # thr=1.0 -> 4 steps (bucket 4); thr=2.0 -> 7 steps (bucket 8)
    for thr_v, n_expect in ((1.0, 4), (2.0, 7)):
        lv, steps = exe.run(
            main, feed={"thr": np.asarray([thr_v], np.float32)},
            fetch_list=[f["loss"], f["w"].steps])
        assert int(np.asarray(steps)) == n_expect, (thr_v, steps)
        s_end = x0 * n_expect
        np.testing.assert_allclose(float(np.asarray(lv)),
                                   (s_end - target) ** 2, rtol=1e-4)
    # two trip-count buckets -> two compiled variants of the program
    uid = main.desc.uid
    bucketed = [k for k in exe._cache if k[0] == uid]
    assert len(bucketed) == 2


def test_forward_only_unbounded_while_needs_no_probe():
    # without grads the loop stays a lax.while_loop and no probe entry
    # is created
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        i = layers.fill_constant([1], "float32", 0.0)
        lim = layers.data("lim", [1], dtype="float32")
        cond = cf.less_than_v(i, lim)
        w = cf.While(cond)
        with w.block():
            layers.increment(i, value=1.0, in_place=True)
            cf.less_than_v(i, lim, cond=cond)
    exe = pt.Executor()
    exe.run(startup)
    iv, steps = exe.run(main, feed={"lim": np.asarray([5.0], np.float32)},
                        fetch_list=[i, w.steps])
    assert float(np.asarray(iv).reshape(())) == 5.0
    assert int(np.asarray(steps)) == 5
    assert not exe._probe_cache


def test_two_dynamic_whiles_in_one_program():
    """Two unbounded Whiles with different data-dependent trip counts
    in ONE program: the probe measures both, and both gradients flow."""
    lr = 0.01
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.create_parameter(
            shape=[1], dtype="float32", name="xp2",
            default_initializer=pt.initializer.ConstantInitializer(0.4))
        thr1 = layers.data("thr1", [1], dtype="float32")
        thr2 = layers.data("thr2", [1], dtype="float32")

        def loop(thr):
            s = layers.fill_constant([1], "float32", 0.0)
            s.stop_gradient = False
            cond = cf.less_than_v(s, thr)
            w = cf.While(cond)
            with w.block():
                t = layers.elementwise_add(s, x)
                layers.assign(t, output=s)
                cf.less_than_v(s, thr, cond=cond)
            return s, w

        s1, w1 = loop(thr1)
        s2, w2 = loop(thr2)
        loss = layers.reduce_sum(layers.elementwise_add(
            layers.square(s1), layers.square(s2)))
        pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    x0 = 0.4
    lv, n1, n2 = exe.run(
        main, feed={"thr1": np.asarray([1.0], np.float32),
                    "thr2": np.asarray([2.0], np.float32)},
        fetch_list=[loss, w1.steps, w2.steps])
    # x=0.4: s1 walks to 1.2 in 3 steps, s2 to 2.0 in 5 steps
    assert int(np.asarray(n1)) == 3 and int(np.asarray(n2)) == 5
    np.testing.assert_allclose(float(np.asarray(lv)),
                               1.2 ** 2 + 2.0 ** 2, rtol=1e-5)
    # d loss / dx = 2*s1*n1 + 2*s2*n2
    g_expect = 2 * 1.2 * 3 + 2 * 2.0 * 5
    x1 = float(np.asarray(pt.global_scope().get("xp2")).reshape(()))
    np.testing.assert_allclose((x0 - x1) / lr, g_expect, rtol=1e-4)


def test_stateful_op_in_probe_prefix_raises():
    """A channel/select/go op before a differentiated unbounded While
    would be re-executed by the trip-count probe (firing twice per
    step) — the executor must reject the combination explicitly rather
    than silently desyncing the channel protocol."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.create_parameter(
            shape=[1], dtype="float32", name="xp_st",
            default_initializer=pt.initializer.ConstantInitializer(0.3))
        ch = layers.make_channel(capacity=2)
        v = layers.fill_constant([1], "float32", 1.0)
        layers.channel_send(ch, v)          # stateful op in the prefix
        thr = layers.data("thr_st", [1], dtype="float32")
        s = layers.fill_constant([1], "float32", 0.0)
        s.stop_gradient = False
        cond = cf.less_than_v(s, thr)
        w = cf.While(cond)
        with w.block():
            t = layers.elementwise_add(s, x)
            layers.assign(t, output=s)
            cf.less_than_v(s, thr, cond=cond)
        loss = layers.reduce_sum(layers.square(s))
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    with pytest.raises(RuntimeError, match="stateful"):
        exe.run(main, feed={"thr_st": np.asarray([1.0], np.float32)},
                fetch_list=[loss])


def test_nested_dynamic_while_gradient_matches_finite_differences():
    """A dynamic-trip-count While NESTED inside another dynamic While
    trains: the outer loop max-accumulates the
    inner loop's per-iteration trip count into its NestedSteps output,
    the probe reads one bound per nesting level, and the program
    recompiles as nested masked scans (reference: while_op.cc:96-109
    step scopes, which nest freely)."""
    lr, x0, target = 0.05, 0.3, 2.0
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.create_parameter(
            shape=[1], dtype="float32", name="xp_nest",
            default_initializer=pt.initializer.ConstantInitializer(x0))
        thr_out = layers.data("thr_out", [1], dtype="float32")
        thr_in = layers.data("thr_in", [1], dtype="float32")
        s = layers.fill_constant([1], "float32", 0.0)
        s.stop_gradient = False
        cond_o = cf.less_than_v(s, thr_out)
        w_o = cf.While(cond_o)
        with w_o.block():
            t = layers.fill_constant([1], "float32", 0.0)
            t.stop_gradient = False
            cond_i = cf.less_than_v(t, thr_in)
            w_i = cf.While(cond_i)          # NO max_steps, nested
            with w_i.block():
                layers.assign(layers.elementwise_add(t, x), output=t)
                cf.less_than_v(t, thr_in, cond=cond_i)
            layers.assign(layers.elementwise_add(s, t), output=s)
            cf.less_than_v(s, thr_out, cond=cond_o)
        tgt = layers.fill_constant([1], "float32", target)
        loss = layers.reduce_sum(layers.square(layers.elementwise_sub(
            s, tgt)))
        pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)

    def host(x, to, ti):
        s = 0.0
        n_out = 0
        while s < to:
            t = 0.0
            while t < ti:
                t += x
            s += t
            n_out += 1
        return (s - target) ** 2, n_out

    to, ti = 2.0, 1.0
    lv, n_out = exe.run(
        main, feed={"thr_out": np.asarray([to], np.float32),
                    "thr_in": np.asarray([ti], np.float32)},
        fetch_list=[loss, w_o.steps])
    # x=0.3: inner 4 steps -> t=1.2; outer: 1.2, 2.4 -> 2 iterations
    assert int(np.asarray(n_out)) == 2
    np.testing.assert_allclose(float(np.asarray(lv)),
                               (2.4 - target) ** 2, rtol=1e-5)
    x1 = float(np.asarray(pt.global_scope().get("xp_nest")).reshape(()))
    eps = 1e-3
    fp, _ = host(x0 + eps, to, ti)
    fm, _ = host(x0 - eps, to, ti)
    g_fd = (fp - fm) / (2 * eps)
    np.testing.assert_allclose((x0 - x1) / lr, g_fd, rtol=1e-3)
    # analytic: s = n_out*n_in*x -> dloss/dx = 2*(s-target)*n_out*n_in
    np.testing.assert_allclose((x0 - x1) / lr, 2 * 0.4 * 8, rtol=1e-4)


def test_dynamic_while_inside_dynamic_rnn_trains():
    """A dynamic While inside a DynamicRNN step block: the RNN's scan
    max-accumulates the inner trip count (NestedSteps) and the whole
    construct is differentiable after probe-and-replay."""
    from paddle_tpu.core.lod import LoDTensor

    lr, p0 = 0.02, 0.25
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        p = layers.create_parameter(
            shape=[1], dtype="float32", name="p_drnn_nest",
            default_initializer=pt.initializer.ConstantInitializer(p0))
        x = layers.data("x", [1], dtype="float32", lod_level=1)
        drnn = cf.DynamicRNN()
        with drnn.block():
            x_t = drnn.step_input(x)        # [1, 1] (batch 1)
            prev = drnn.memory(shape=[1], value=0.0)
            # inner: walk t up by p until it reaches this step's x_t
            t = layers.fill_constant([1], "float32", 0.0)
            t.stop_gradient = False
            thr = layers.reshape(x_t, [1])
            cond_i = cf.less_than_v(t, thr)
            w_i = cf.While(cond_i)
            with w_i.block():
                layers.assign(layers.elementwise_add(t, p), output=t)
                cf.less_than_v(t, thr, cond=cond_i)
            nxt = layers.elementwise_add(prev, layers.reshape(t, [1, 1]))
            drnn.update_memory(prev, nxt)
            drnn.output(nxt)
        _ = drnn()
        last = drnn.last_memory()
        loss = layers.reduce_sum(layers.square(last))
        pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)

    seq = np.asarray([[0.4], [0.9], [0.2]], np.float32)   # one sequence
    rag = LoDTensor.from_sequences([seq])
    (lv,) = exe.run(main, feed={"x": rag}, fetch_list=[loss])

    def host(p):
        s = 0.0
        for xt in (0.4, 0.9, 0.2):
            t = 0.0
            while t < xt:
                t += p
            s += t
        return s * s

    np.testing.assert_allclose(float(np.asarray(lv)), host(p0),
                               rtol=1e-5)
    p1 = float(np.asarray(pt.global_scope().get("p_drnn_nest"))
               .reshape(()))
    eps = 1e-3
    g_fd = (host(p0 + eps) - host(p0 - eps)) / (2 * eps)
    np.testing.assert_allclose((p0 - p1) / lr, g_fd, rtol=1e-3)


def test_dynamic_while_inside_cond_branch():
    """A dynamic While inside a lax.cond branch (itself inside an outer
    dynamic While) must run AND train: branch trip counts surface as
    extra cond outputs (a tracer may not leak from a branch trace), so
    the outer loop's max-accumulation and the probe see them."""
    lr, x0 = 0.001, 0.3
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.create_parameter(
            shape=[1], dtype="float32", name="xp_cond",
            default_initializer=pt.initializer.ConstantInitializer(x0))
        thr_out = layers.data("thr_out", [1], dtype="float32")
        thr_in = layers.data("thr_in", [1], dtype="float32")
        s = layers.fill_constant([1], "float32", 0.0)
        s.stop_gradient = False
        cond_o = cf.less_than_v(s, thr_out)
        w_o = cf.While(cond_o)
        with w_o.block():
            half = layers.fill_constant([1], "float32", 0.6)
            pred = cf.less_than_v(s, half)   # branch varies by iteration

            def walk():
                # dynamic inner While lives in the TRUE branch only
                t = layers.fill_constant([1], "float32", 0.0)
                t.stop_gradient = False
                cond_i = cf.less_than_v(t, thr_in)
                w_i = cf.While(cond_i)
                with w_i.block():
                    layers.assign(layers.elementwise_add(t, x), output=t)
                    cf.less_than_v(t, thr_in, cond=cond_i)
                return t

            def fixed():
                return layers.scale(x, scale=2.0)

            inc = cf.cond_op(pred, walk, fixed)
            layers.assign(layers.elementwise_add(s, inc), output=s)
            cf.less_than_v(s, thr_out, cond=cond_o)
        loss = layers.reduce_sum(layers.square(s))
        pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)

    def host(xv, to, ti):
        s = 0.0
        while s < to:
            if s < 0.6:
                t = 0.0
                while t < ti:
                    t += xv
                s += t
            else:
                s += 2 * xv
        return s * s

    to, ti = 1.5, 1.0
    # x=0.3: iter1 s<0.6 -> inner walks to 1.2, s=1.2; iter2 s>=0.6 ->
    # s=1.8 >= 1.5 -> 2 outer iterations
    lv, n = exe.run(main,
                    feed={"thr_out": np.asarray([to], np.float32),
                          "thr_in": np.asarray([ti], np.float32)},
                    fetch_list=[loss, w_o.steps])
    assert int(np.asarray(n)) == 2
    np.testing.assert_allclose(float(np.asarray(lv)), host(x0, to, ti),
                               rtol=1e-5)
    x1 = float(np.asarray(pt.global_scope().get("xp_cond")).reshape(()))
    eps = 1e-3
    g_fd = (host(x0 + eps, to, ti) - host(x0 - eps, to, ti)) / (2 * eps)
    np.testing.assert_allclose((x0 - x1) / lr, g_fd, rtol=1e-3)


def test_dynamic_while_inside_if_else_trains():
    """A dynamic While inside an IfElse branch (dense both-branch
    lowering): both branches execute, so the op reports the max of the
    branch trip counts and the probe bakes the bound."""
    lr, x0 = 0.001, 0.3
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.create_parameter(
            shape=[1], dtype="float32", name="xp_ifelse",
            default_initializer=pt.initializer.ConstantInitializer(x0))
        thr = layers.data("thr", [1], dtype="float32")
        sel = layers.data("sel", [1], dtype="float32")
        cond = cf.less_than_v(sel, layers.fill_constant(
            [1], "float32", 0.5))
        ie = cf.IfElse(cond)
        with ie.true_block():
            t = layers.fill_constant([1], "float32", 0.0)
            t.stop_gradient = False
            cond_i = cf.less_than_v(t, thr)
            w_i = cf.While(cond_i)          # NO max_steps
            with w_i.block():
                layers.assign(layers.elementwise_add(t, x), output=t)
                cf.less_than_v(t, thr, cond=cond_i)
            ie.output(t)
        with ie.false_block():
            ie.output(layers.scale(x, scale=3.0))
        out = ie()
        loss = layers.reduce_sum(layers.square(out))
        pt.optimizer.SGDOptimizer(learning_rate=lr).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)

    def host(xv, sel_v):
        if sel_v < 0.5:
            t = 0.0
            while t < 1.0:
                t += xv
            return t * t
        return (3 * xv) ** 2

    for sel_v in (0.0, 1.0):    # true branch taken, then false branch
        x_before = float(np.asarray(
            pt.global_scope().get("xp_ifelse")).reshape(()))
        (lv,) = exe.run(main,
                        feed={"thr": np.asarray([1.0], np.float32),
                              "sel": np.asarray([sel_v], np.float32)},
                        fetch_list=[loss])
        np.testing.assert_allclose(float(np.asarray(lv)),
                                   host(x_before, sel_v), rtol=1e-4)
        x_after = float(np.asarray(
            pt.global_scope().get("xp_ifelse")).reshape(()))
        eps = 1e-3
        g_fd = (host(x_before + eps, sel_v)
                - host(x_before - eps, sel_v)) / (2 * eps)
        np.testing.assert_allclose((x_before - x_after) / lr, g_fd,
                                   rtol=1e-3)
