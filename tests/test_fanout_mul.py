"""`fanout_mul`: the projections that read ONE activation (self-attention's
q, k, v; cross-attention's k, v) as one op, so that under a mesh that
shards their output features the input gradients are summed on the shard
and cross the 'model' axis as ONE all-reduce (ISSUE 50).

The benchmark cannot see a wrong gradient on its mesh cell (its check is
the forward's loss), so these carry the proof that the update is the
same: a (2,2) mesh step against the one-device step, the collectives of
the compiled step counted in TENSORS, the site counter, and the
parameter list the benchmark's reseeding walks by position.
"""
import itertools

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.models import transformer
from paddle_tpu.observability import default_registry
from paddle_tpu.parallel import collective_audit as ca
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.executor import ParallelExecutor, ShardingSpec

WIDTHS = dict(src_vocab=96, trg_vocab=96, max_len=16, n_head=4, d_model=32,
              d_inner=64)
BATCH, SEQ = 4, 16


def _build(n_layer, **kw):
    pt.reset_default_programs()
    pt.reset_global_scope()
    return transformer.build_train(n_layer=n_layer, **dict(WIDTHS, **kw))


def _mesh_executor(main):
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    sharding = ShardingSpec(specs=transformer.tp_param_specs(main),
                            feed_axis="data")
    sharding.specs["pos_ids"] = P()
    return ParallelExecutor(mesh=mesh, sharding=sharding), mesh


def _sites():
    fam = default_registry().get("paddle_tpu_fanout_sites_total")
    return {labels: child.value
            for labels, child in (fam.samples() if fam is not None else ())}


def _sites_since(before):
    return {k: v - before.get(k, 0) for k, v in _sites().items()
            if v - before.get(k, 0)}


def _one_adam_step(on_mesh):
    """Two encoder and two decoder layers, every parameter and the batch
    from fixed seeds: (gradients, parameters before, after) by name."""
    main, startup, fetch = _build(2)
    exe = _mesh_executor(main)[0] if on_mesh else pt.Executor()
    exe.run(startup)
    scope, rng = pt.global_scope(), np.random.RandomState(0)
    names = [p.name for p in main.all_parameters()]
    for p in main.all_parameters():    # no zero bias: Adam's first step
        scope.set(p.name, (0.1 * rng.standard_normal(p.shape)).astype(
            np.float32))               # is lr * sign(g) wherever g != 0
    before = {n: np.array(scope.get(n)) for n in names}
    feed = {n: rng.randint(1, 96, (BATCH, SEQ, 1)).astype(np.int64)
            for n in ("src_ids", "trg_ids", "trg_labels")}
    feed["pos_ids"] = np.arange(SEQ).astype(np.int64)
    grads = exe.run(main, feed=feed,
                    fetch_list=[n + "@GRAD" for n in names])
    after = {n: np.array(scope.get(n)) for n in names}
    return dict(zip(names, map(np.asarray, grads))), before, after


@pytest.fixture(scope="module", params=[False, True], ids=["f32", "amp"])
def steps(request):
    with pt.amp.amp_guard(request.param):
        one, mesh = _one_adam_step(False), _one_adam_step(True)
    pt.reset_global_scope()
    return request.param, one, mesh


def _site_of(name):
    """Which projections a parameter of the two-layer model belongs to:
    tp_col_qkv.w_0-8 and 12-14 are self-attention's q, k, v (encoder
    layers 0 and 1, decoder layers 0 and 1), 9-11 and 15-17 the
    cross-attention's."""
    if not name.startswith("tp_col_qkv."):
        return "rest"
    return "cross" if int(name.rsplit("_", 1)[1]) in (
        9, 10, 11, 15, 16, 17) else "self"


@pytest.mark.parametrize("site", ["self", "cross", "rest"])
def test_a_mesh_step_updates_what_one_device_updates(steps, site):
    """One Adam step through ParallelExecutor on ('data','model') = (2,2)
    under tp_param_specs against the plain Executor: in float32 every
    parameter after the step to 1e-5 and every gradient to 1e-4; under AMP
    (bfloat16 products, whose rounding flips the sign of a gradient
    within it) the first-order descent the mesh's update buys, as a
    share of the one-device update's (chipbench/reference.py
    descent_share), in every array."""
    from chipbench.reference import descent_share
    amp, (g1, b1, a1), (g2, b2, a2) = steps
    names = [n for n in a1 if _site_of(n) == site]
    assert len(names) == {"self": 12, "cross": 6, "rest": 46}[site]
    if not amp:
        for n in names:
            # (a q or k projection's gradient is a difference of nearly
            # equal terms at fresh weights: 3e-8 at most, read to 3e-5)
            for one, mesh, tol in ((g1[n], g2[n], 1e-4),
                                   (a1[n], a2[n], 1e-5)):
                assert np.abs(one - mesh).max() <= \
                    tol * np.abs(one).max(), n
        return
    share = descent_share([g1[n] for n in names],
                          [a2[n] - b2[n] for n in names],
                          [a1[n] - b1[n] for n in names])
    assert min(share["per_array"]) >= 0.98, dict(
        zip(names, share["per_array"]))


def _compiled_step(n_layer):
    from paddle_tpu.parallel.scaling_model import aot_compiled_hlo
    main, startup, fetch = _build(n_layer)
    exe, mesh = _mesh_executor(main)
    pt.Executor().run(startup)
    ids = jax.ShapeDtypeStruct((BATCH, SEQ, 1), np.int64)
    feeds = dict.fromkeys(("src_ids", "trg_ids", "trg_labels"), ids)
    feeds["pos_ids"] = jax.ShapeDtypeStruct((SEQ,), np.int64)
    try:
        return aot_compiled_hlo(exe, main, feeds, [fetch["loss"]]), mesh
    finally:
        pt.reset_global_scope()


@pytest.mark.parametrize("n_layer", [1, 2])
def test_the_model_axis_carries_one_sum_a_shared_input(n_layer):
    """The compiled (2,2) step, read through the collective audit:
    activation-sized all-reduced TENSORS over 'model' (tuple elements,
    not instructions: the combiner differs by backend) are 2 forward + 2
    backward an encoder layer (attention, feed-forward) and 3 forward +
    4 backward a decoder layer (self-attention, cross-attention's q,
    its k and v TOGETHER, the feed-forward pair): 2 + 4 and 3 + 7
    before ISSUE 50, one a projection. And the stacked form reshards
    nothing: no activation-sized collective of another kind (what a
    concatenation along the sharded axis would cost)."""
    hlo, mesh = _compiled_step(n_layer)
    rows = BATCH // 2 * SEQ * WIDTHS["d_model"]     # a device's share
    forward = backward = 0
    for c in ca.classify(ca.parse_collectives(hlo), mesh):
        big = [t for t in c.tensors if ca.tensor_elements(t) >= rows]
        if "model" not in c.axes or not big:
            continue
        assert c.kind == "all-reduce", (c, c.tensors)
        if "__vjp__" in c.op_name:
            backward += len(big)
        else:
            forward += len(big)
    assert (forward, backward) == ((2 + 3) * n_layer, (2 + 4) * n_layer)
    carried = ca.tensors_over(hlo, mesh, "model", min_elements=rows)
    assert {kind for kind, _ in carried} == {"all-reduce"}
    assert sum(carried.values()) == 11 * n_layer
    # the gradients still cross 'data'
    ca.assert_collectives(ca.inventory(hlo, mesh),
                          [(("all-reduce",), "data")])


def _trace_step(exe, main, fetch):
    """Trace the step program (no compile, no run): the sites count."""
    sig = tuple(sorted(
        [(n, ((BATCH, SEQ, 1), "int64"))
         for n in ("src_ids", "trg_ids", "trg_labels")]
        + [("pos_ids", ((SEQ,), "int64"))]))
    scope = pt.global_scope()
    step = exe._compile(main.desc, main.desc.block(0), sig,
                        [fetch["loss"].name], scope)
    sds = [jax.ShapeDtypeStruct(s, np.int64) for _, (s, _) in sig]

    def state(names):
        return [jax.ShapeDtypeStruct(scope.get(n).shape,
                                     scope.get(n).dtype) for n in names]
    step.jitted.trace(sds, state(step.ro_names), state(step.rw_names),
                      jax.ShapeDtypeStruct((), np.int32))


def test_the_site_counter_says_which_path_a_step_took():
    """A step of the benchmark's depth (6 + 6 layers) traced for the
    (2,2) mesh reads 18 `stacked` sites, 12 of three products and 6 of
    two, and nothing `separate`; the same program traced for one device
    reads the same 18 sites `separate` and none `stacked`: no mesh, the
    three products `mul` made."""
    main, startup, fetch = _build(6)
    pt.Executor().run(startup)
    try:
        before = _sites()
        _trace_step(_mesh_executor(main)[0], main, fetch)
        assert _sites_since(before) == {("3", "stacked"): 12,
                                        ("2", "stacked"): 6}
        before = _sites()
        _trace_step(pt.Executor(), main, fetch)
        assert _sites_since(before) == {("3", "separate"): 12,
                                        ("2", "separate"): 6}
    finally:
        pt.reset_global_scope()


def test_a_replicated_weight_keeps_the_separate_products():
    """The path follows the weights' sharding, not the mesh alone: under
    a mesh with no spec for the projections (pure data parallelism)
    there is nothing to merge."""
    main, startup, fetch = _build(1)
    pt.Executor().run(startup)
    try:
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        before = _sites()
        _trace_step(ParallelExecutor(mesh=mesh, sharding=ShardingSpec(
            feed_axis="data")), main, fetch)
        assert _sites_since(before) == {("3", "separate"): 2,
                                        ("2", "separate"): 1}
    finally:
        pt.reset_global_scope()


# `main.all_parameters()` of build_train at the commit before ISSUE 50
# (one encoder and one decoder layer): chipbench/weights.reseed and
# chipbench/reference.py walk the list by position and by shape
_LAYER = [("tp_col_ffn.w_{f}", (32, 64)), ("tp_col_ffn.w_{f1}", (64,)),
          ("tp_row_ffn.w_{f}", (64, 32)), ("tp_row_ffn.w_{f1}", (32,))]
PARAMETERS_BEFORE = (
    [("embedding_0.w_0", (96, 32))]
    + [(f"tp_col_qkv.w_{i}", (32, 32)) for i in range(3)]
    + [("tp_row_proj.w_0", (32, 32)),
       ("layer_norm_0.w_0", (32,)), ("layer_norm_0.w_1", (32,))]
    + [(n.format(f=0, f1=1), s) for n, s in _LAYER]
    + [("layer_norm_1.w_0", (32,)), ("layer_norm_1.w_1", (32,)),
       ("embedding_1.w_0", (96, 32))]
    + [(f"tp_col_qkv.w_{i}", (32, 32)) for i in range(3, 6)]
    + [("tp_row_proj.w_1", (32, 32)),
       ("layer_norm_2.w_0", (32,)), ("layer_norm_2.w_1", (32,))]
    + [(f"tp_col_qkv.w_{i}", (32, 32)) for i in range(6, 9)]
    + [("tp_row_proj.w_2", (32, 32)),
       ("layer_norm_3.w_0", (32,)), ("layer_norm_3.w_1", (32,))]
    + [(n.format(f=2, f1=3), s) for n, s in _LAYER]
    + [("layer_norm_4.w_0", (32,)), ("layer_norm_4.w_1", (32,)),
       ("fc_0.w_0", (32, 96)), ("fc_0.w_1", (96,))])


def test_the_parameters_are_what_three_fc_calls_made():
    main, startup, _ = _build(1, n_head=2)
    assert [(p.name, tuple(p.shape)) for p in main.all_parameters()] == \
        PARAMETERS_BEFORE
    # and the program: one fan-out op a shared input, `mul` for
    # cross-attention's q, every weight in tp_param_specs' reach
    ops = [op for op in main.desc.block(0).ops if op.type == "fanout_mul"]
    assert [len(op.input("Y")) for op in ops] == [3, 3, 2]
    specs = transformer.tp_param_specs(main)
    assert all(specs[n] == P(None, "model")
               for op in ops for n in op.input("Y"))
    inits = {op.output_names()[0]: op.type
             for op in startup.desc.block(0).ops}
    assert len({inits[f"tp_col_qkv.w_{i}"] for i in range(9)}) == 1


@pytest.mark.parametrize("amp", [False, True], ids=["f32", "amp"])
@pytest.mark.parametrize("k", [2, 3])
def test_both_paths_give_the_products_mul_gives(k, amp):
    """The rule's two branches against `_mxu_matmul`, values and
    gradients: the stacked contraction is the same k products and the
    same sum in another order (float32 accumulation, ONE rounding)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.math_ops import _mxu_fanout, _mxu_matmul
    rng = np.random.RandomState(k)
    x = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    ws = [jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
          for _ in range(k)]

    def loss(f):
        return lambda x, ws: sum(
            (o.astype(jnp.float32) * (i + 1)).sum()
            for i, o in enumerate(f(x, ws)))

    with pt.amp.amp_guard(amp):
        separate = lambda x, ws: [_mxu_matmul(x, w) for w in ws]  # noqa: E731
        got, want = _mxu_fanout(x, ws), separate(x, ws)
        g_got = jax.grad(loss(_mxu_fanout), argnums=(0, 1))(x, ws)
        g_want = jax.grad(loss(separate), argnums=(0, 1))(x, ws)
    assert all(a.dtype == b.dtype == (jnp.bfloat16 if amp else jnp.float32)
               for a, b in zip(got, want))
    tol = 2e-2 if amp else 1e-5
    for a, b in itertools.chain(
            zip(got, want), zip(jax.tree_util.tree_leaves(g_got),
                                jax.tree_util.tree_leaves(g_want))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()
