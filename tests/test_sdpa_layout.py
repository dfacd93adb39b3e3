"""The attention op's ``layout`` attr (ISSUE 47): a site whose Q, K, V
and Out are sequence-major ("bshd", [b, S, h, d] — what a reshape of a
projection's output gives), as ``models/transformer.py
multi_head_attention`` now places it. The program holds no transpose op
around the site and computes what the head-major form computed: first
loss, gradients and first Adam update, under the flash kernels (forced,
interpret mode here) and under the composition; under a (2, 2) mesh the
site runs per shard of batch and heads; cached decode and sequence
parallelism refuse the attr; the cost model books a site the same in
both layouts; the site counters carry the layout and the heads a block.
"""
import collections

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis import cost_model
from paddle_tpu.core.registry import grad_var_name
from paddle_tpu.models import transformer
from paddle_tpu.observability import default_registry
from paddle_tpu.ops import nn_ops

KNOB = {"flash": "force", "composed": "0"}
# 64-wide heads: two fill a 128-lane block
MODEL = dict(n_layer=1, n_head=4, d_model=256, d_inner=128)
S, V, LR = 16, 40, 1e-3


def _sites(family):
    fam = default_registry().get(f"paddle_tpu_{family}_sites_total")
    return collections.Counter() if fam is None else collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


def _head_major_attention(q_in, k_in, v_in, d_model, n_head, mask=None,
                          dropout_rate=0.0, causal=False, seq_axis=None,
                          seq_impl="ring"):
    """multi_head_attention as it was built before the attr: the heads
    split and merged by transpose ops around a head-major site."""
    d_key = d_model // n_head

    def project(x):
        return layers.fc(x, size=d_model, num_flatten_dims=2,
                         bias_attr=False, name="tp_col_qkv")

    def split(x):
        return layers.transpose(
            layers.reshape(x, [0, 0, n_head, d_key]), [0, 2, 1, 3])

    heads = transformer._sdpa_op(split(project(q_in)), split(project(k_in)),
                                 split(project(v_in)), mask, causal)
    merged = layers.reshape(layers.transpose(heads, [0, 2, 1, 3]),
                            [0, 0, d_model])
    return layers.fc(merged, size=d_model, num_flatten_dims=2,
                     bias_attr=False, name="tp_row_proj")


def _batch():
    rng = np.random.RandomState(0)

    def ids(lengths):
        x = rng.randint(1, V, (len(lengths), S, 1)).astype(np.int64)
        for row, n in enumerate(lengths):
            x[row, n:] = 0
        return x
    trg = ids([S, S - 3, S // 2, S - 1])
    lbl = np.concatenate([trg[:, 1:], np.zeros_like(trg[:, :1])], axis=1)
    return {"src_ids": ids([S - 5, S // 2, S, S - 2]), "trg_ids": trg,
            "trg_labels": lbl, "pos_ids": np.arange(S, dtype=np.int64)}


def _first_step(weights=None):
    """Build the train program, start it (from `weights` if given) and
    run one Adam step: (program, weights before, loss, grads, weights
    after)."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    main, startup, fetch = transformer.build_train(
        src_vocab=V, trg_vocab=V, max_len=S, lr=LR, **MODEL)
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    names = [p.name for p in main.all_parameters()]
    if weights is not None:
        assert sorted(weights) == sorted(names)
        for n in names:
            scope.set(n, weights[n])
    before = {n: np.array(scope.get(n)) for n in names}
    loss, *grads = exe.run(
        main, feed=_batch(),
        fetch_list=[fetch["loss"]] + [grad_var_name(n) for n in names])
    after = {n: np.array(scope.get(n)) for n in names}
    return (main, before, float(np.asarray(loss).reshape(())),
            dict(zip(names, map(np.asarray, grads))), after)


@pytest.mark.parametrize("path", ["flash", "composed"])
def test_first_loss_and_adam_update_equal_the_head_major_forms(
        monkeypatch, path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    sdpa, fwd, bwd = _sites("sdpa"), _sites("flash_fwd"), \
        _sites("flash_bwd")
    main, weights, loss, grads, after = _first_step()
    # the site counters of a traced step: every site sequence-major,
    # two heads a block under the kernels, none transposed back
    assert dict(_sites("sdpa") - sdpa) == {
        (path, "key_row", "0", "0", "1", "bshd"): 2,
        (path, "key_row", "1", "0", "1", "bshd"): 1}
    # (the build's shape inference traces no kernel: the composition
    # says Out's shape)
    # (4 batch rows of one tile each: the short-sequence plan's 4 a step)
    kernels = ({("resident", "0", "1", "2", "4"): 3} if path == "flash"
               else {})
    assert dict(_sites("flash_bwd") - bwd) == kernels
    assert dict(_sites("flash_fwd") - fwd) == kernels
    sites = [op for op in main.global_block().ops
             if op.type == "scaled_dot_product_attention"]
    assert [op.attr("layout") for op in sites] == ["bshd"] * 3

    monkeypatch.setattr(transformer, "multi_head_attention",
                        _head_major_attention)
    sdpa = _sites("sdpa")
    old_main, _, old_loss, old_grads, old_after = _first_step(weights)
    assert dict(_sites("sdpa") - sdpa) == {
        (path, "key_row", "0", "0", "1", "bhsd"): 2,
        (path, "key_row", "1", "0", "1", "bhsd"): 1}
    assert sum(op.type == "transpose"
               for op in old_main.global_block().ops) == sum(
        op.type == "transpose" for op in main.global_block().ops) + 12

    np.testing.assert_allclose(loss, old_loss, rtol=1e-6)
    for name in weights:
        scale = max(float(np.abs(old_grads[name]).max()), 1e-6)
        np.testing.assert_allclose(grads[name], old_grads[name], rtol=1e-4,
                                   atol=2e-6 * scale, err_msg=name)
        # Adam's first step is lr * g / (|g| + eps): where the gradient
        # is not noise the two updates are one number
        solid = np.abs(old_grads[name]) > 1e-4 * scale
        step, old_step = (after[name] - weights[name],
                          old_after[name] - weights[name])
        np.testing.assert_allclose(step[solid], old_step[solid],
                                   atol=1e-3 * LR, err_msg=name)
        assert np.abs(step - old_step).max() <= 2 * LR, name


def test_the_attention_builder_places_no_transpose_op():
    pt.reset_default_programs()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [S, 256], dtype="float32")
        mem = layers.data("mem", [2 * S, 256], dtype="float32")
        out = transformer.multi_head_attention(x, mem, mem, 256, 4)
    types = [op.type for op in main.global_block().ops]
    assert "transpose" not in types
    # q by `mul`; k and v, which read ONE memory, by one fan-out op
    assert types == ["mul", "fanout_mul"] + ["reshape"] * 3 + [
        "scaled_dot_product_attention", "reshape", "mul"]
    assert tuple(out.shape)[1:] == (S, 256)
    (site,) = [op for op in main.global_block().ops
               if op.type == "scaled_dot_product_attention"]
    block = main.global_block()
    # Out's inferred shape is the statement of Q's layout: [b, Sq, h, dv]
    assert tuple(block.var(site.output("Out")[0]).shape)[1:] == (S, 4, 64)
    # under sequence parallelism the arrays stay head-major
    with pt.program_guard(pt.Program(), pt.Program()):
        x = layers.data("x", [S, 256], dtype="float32")
        transformer.multi_head_attention(x, x, x, 256, 4, seq_axis="seq")
        types = [op.type for op in
                 pt.default_main_program().global_block().ops]
    assert types.count("transpose") == 4


def test_under_a_mesh_the_site_runs_per_shard_of_batch_and_heads(
        monkeypatch):
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.executor import ParallelExecutor, ShardingSpec
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", "force")
    _, weights, loss, _, _ = _first_step()

    seen, real = [], nn_ops._per_shard_attention

    def spy(attend, mesh, q, k, v, mask, batch_axis, head_axis,
            head_dim=1):
        seen.append((q.shape, head_dim))
        return real(attend, mesh, q, k, v, mask, batch_axis, head_axis,
                    head_dim)

    monkeypatch.setattr(nn_ops, "_per_shard_attention", spy)
    pt.reset_default_programs()
    pt.reset_global_scope()
    main, startup, fetch = transformer.build_train(
        src_vocab=V, trg_vocab=V, max_len=S, lr=LR, **MODEL)
    pt.Executor().run(startup)
    for n, w in weights.items():
        pt.global_scope().set(n, w)
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    exe = ParallelExecutor(mesh=mesh, sharding=ShardingSpec(
        specs=transformer.tp_param_specs(main), feed_axis="data"))
    fwd = _sites("flash_fwd")
    (got,) = exe.run(main, feed=_batch(), fetch_list=[fetch["loss"]])
    # 4 rows x 4 heads on (data, model) = (2, 2): a shard holds 2 rows
    # and 2 heads, one block; forward and again under the grad op
    assert seen and all(dim == 2 for _, dim in seen)
    assert {shape for shape, _ in seen} == {(4, S, 4, 64)}
    assert set(_sites("flash_fwd") - fwd) == {
        ("resident", "0", "1", "2", "2")}      # a shard's 2 rows a step
    np.testing.assert_allclose(float(np.asarray(got).reshape(())), loss,
                               rtol=2e-5)


def _site(q_shape, layout, **inputs_and_attrs):
    pt.reset_default_programs()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", list(q_shape), dtype="float32")
        extra = {k: v(q) if callable(v) else v
                 for k, v in inputs_and_attrs.items()}
        out = transformer._sdpa_op(q, q, q, None, False, layout=layout,
                                   **extra)
    return main, out


def test_cached_decode_and_sequence_parallelism_refuse_the_attr():
    """The rule refuses when the step is traced (the build's shape
    inference keeps a rule's refusal to itself)."""
    q = np.zeros((2, S, 2, 8), np.float32)

    def run(**more):
        main, out = _site((S, 2, 8), **more)
        feed = {"q": q}
        if "kv_len" in more:
            feed["n"] = np.full((2,), 3, np.int32)
        return pt.Executor().run(main, feed=feed, fetch_list=[out])

    with pytest.raises(ValueError, match="KvLen.*bshd"):
        run(layout="bshd", kv_len=lambda q: layers.data(
            "n", [], dtype="int32"))
    with pytest.raises(ValueError, match="seq_axis.*bshd"):
        run(layout="bshd", seq_axis="seq")
    with pytest.raises(ValueError, match="layout"):
        run(layout="sbhd")
    assert run(layout="bshd")[0].shape == (2, S, 2, 8)


def test_the_cost_model_books_a_site_the_same_in_both_layouts():
    b, h, s, d = 3, 4, 32, 64
    costs = {}
    for layout, shape in (("bhsd", (h, s, d)), ("bshd", (s, h, d))):
        main, out = _site(shape, layout)
        assert tuple(out.shape)[1:] == shape
        cost = cost_model.program_cost(main, feed_shapes={"q": (b,) + shape})
        (costs[layout],) = [c for c in cost.ops
                            if c.op_type == "scaled_dot_product_attention"]
    assert costs["bshd"].exact
    assert costs["bshd"].flops == costs["bhsd"].flops == \
        4 * b * h * s * s * d + 5 * b * h * s * s
    assert costs["bshd"].bytes_accessed == costs["bhsd"].bytes_accessed
    # a cross site: the keys' rows are read off K by the attr too
    pt.reset_default_programs()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [s, h, d], dtype="float32")
        k = layers.data("k", [2 * s, h, d], dtype="float32")
        transformer._sdpa_op(q, k, k, None, False, layout="bshd")
    cost = cost_model.program_cost(main, feed_shapes={
        "q": (b, s, h, d), "k": (b, 2 * s, h, d)})
    (cross,) = [c for c in cost.ops
                if c.op_type == "scaled_dot_product_attention"]
    assert cross.flops == 2 * costs["bshd"].flops


@pytest.mark.parametrize("path", ["flash", "composed"])
def test_the_op_computes_the_same_site_in_both_layouts(monkeypatch, path):
    """One site with a key-row mask and the causal flag, its output and
    the gradients of q, k, v, in both layouts through both paths."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    rng = np.random.RandomState(1)
    b, h, s, d = 2, 2, 24, 64
    arrays = {n: rng.randn(b, h, s, d).astype(np.float32) for n in "qkv"}
    mask = np.where(np.arange(s)[None, :] < np.array([s, s - 7])[:, None],
                    0.0, -1e9).astype(np.float32)[:, None, None, :]

    def run(layout):
        pt.reset_default_programs()
        main, startup = pt.Program(), pt.Program()
        shape = [h, s, d] if layout == "bhsd" else [s, h, d]
        with pt.program_guard(main, startup):
            q, k, v = (layers.data(n, shape, stop_gradient=False)
                       for n in "qkv")
            m = layers.data("mask", [1, 1, s], dtype="float32")
            out = transformer._sdpa_op(q, k, v, m, True, layout=layout)
            loss = layers.reduce_sum(layers.elementwise_mul(out, out))
            pt.append_backward(loss, program=main)
        feed = {n: x if layout == "bhsd" else x.transpose(0, 2, 1, 3)
                for n, x in arrays.items()}
        got = pt.Executor().run(
            main, feed=dict(feed, mask=mask),
            fetch_list=[out] + [grad_var_name(n) for n in "qkv"])
        return [np.asarray(x) if layout == "bhsd"
                else np.asarray(x).transpose(0, 2, 1, 3) for x in got]

    for name, a, b_ in zip(("out", "dq", "dk", "dv"), run("bshd"),
                           run("bhsd")):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-5,
                                   err_msg=name)
