"""HLO collective audit (parallel/collective_audit.py): the GSPMD
layouts' implicit collectives recovered from compiled HLO, classified
by mesh axis, and asserted — a layout that silently loses its gradient
all-reduce must fail loudly (reference analog: the reference's
explicit, auditable all-reduce graph nodes,
framework/details/nccl_all_reduce_op_handle.cc:30)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel import collective_audit as ca


def test_parse_literal_and_iota_groups():
    hlo = """
  %r1 = f32[128]{0} all-reduce(%x), replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, to_apply=%sum
  %r2 = f32[] all-reduce(%y), channel_id=4, replica_groups=[4,2]<=[2,4]T(1,0), use_global_device_ids=true, to_apply=%sum
  %p1 = f32[2,16]{1,0} collective-permute(%z), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
"""
    cols = ca.parse_collectives(hlo)
    assert [c.kind for c in cols] == ["all-reduce", "all-reduce",
                                      "collective-permute"]
    assert cols[0].groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert cols[0].bytes == 128 * 4
    # iota [4,2]<=[2,4]T(1,0): ids reshaped (2,4), transposed -> (4,2)
    assert cols[1].groups == [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert cols[2].pairs == [(0, 1), (1, 2), (2, 3), (3, 0)]


def test_parse_tpu_tuple_shapes_with_tiled_layouts():
    """A TPU module prints tiled layouts, so a combined (tuple-shaped)
    all-reduce nests parentheses inside its shape — taken from the
    (2,2)-mesh transformer step compiled for v5e in PR 22, where the
    data-axis gradient sync is exactly such an instruction."""
    hlo = """
  %all-reduce.294 = (bf16[8192,512]{1,0:T(8,128)(2,1)S(1)}, bf16[8192,512]{1,0:T(8,128)(2,1)}) all-reduce(%fusion.1234, %fusion.1231), channel_id=39, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add.36.clone
  %ag = f32[2048]{0:T(1024)S(1)} all-gather(%p), channel_id=2, replica_groups=[2,2]<=[4], dimensions={0}
"""
    cols = ca.parse_collectives(hlo)
    assert [c.kind for c in cols] == ["all-reduce", "all-gather"]
    assert cols[0].bytes == 2 * 8192 * 512 * 2
    assert cols[0].groups == [[0, 2], [1, 3]]
    assert cols[1].groups == [[0, 1], [2, 3]]


def test_classification_against_mesh_axes():
    from paddle_tpu.parallel import make_mesh
    import jax
    mesh = make_mesh((2, 2, 2), ("data", "seq", "model"),
                     devices=jax.devices()[:8])
    # groups varying the LAST axis (model): consecutive pairs
    c1 = ca.Collective("all-reduce", 4,
                       groups=[[0, 1], [2, 3], [4, 5], [6, 7]])
    # groups varying the FIRST axis (data): stride-4 pairs
    c2 = ca.Collective("all-reduce", 4,
                       groups=[[0, 4], [1, 5], [2, 6], [3, 7]])
    # groups varying seq+model together
    c3 = ca.Collective("all-gather", 4,
                       groups=[[0, 1, 2, 3], [4, 5, 6, 7]])
    # ring over seq (stride-2 neighbor exchange)
    c4 = ca.Collective("collective-permute", 4,
                       pairs=[(0, 2), (2, 0), (1, 3), (3, 1),
                              (4, 6), (6, 4), (5, 7), (7, 5)])
    out = ca.classify([c1, c2, c3, c4], mesh)
    assert out[0].axes == ("model",)
    assert out[1].axes == ("data",)
    assert out[2].axes == ("seq", "model")
    assert out[3].axes == ("seq",)


def test_classification_composite_and_local_permutes():
    """GSPMD resharding emits permutes whose pairs differ in TWO mesh
    coordinates (an axis swap, e.g. (s=1,m=0)<->(s=0,m=1)) plus
    identity self-pairs; the classifier must attribute them to the
    composite axis set, and tag all-self permutes as local."""
    from paddle_tpu.parallel import make_mesh
    import jax
    mesh = make_mesh((2, 2, 2), ("data", "seq", "model"),
                     devices=jax.devices()[:8])
    # the exact pattern from the transformer dryrun: 1<->2, 5<->6 swap
    # seq and model coords inside each data row; rest are self-pairs
    c1 = ca.Collective("collective-permute", 4,
                       pairs=[(0, 0), (2, 1), (1, 2), (3, 3),
                              (4, 4), (6, 5), (5, 6), (7, 7)])
    c2 = ca.Collective("collective-permute", 4,
                       pairs=[(0, 0), (1, 1), (2, 2), (3, 3)])
    # grouped collective with singleton groups only: also local
    c3 = ca.Collective("all-gather", 4, groups=[[0], [1], [2], [3]])
    # all-reduce with no replica_groups attr: all devices, all axes
    c4 = ca.Collective("all-reduce", 4)
    out = ca.classify([c1, c2, c3, c4], mesh)
    assert out[0].axes == ("seq", "model")
    assert out[1].axes == ("local",)
    assert out[2].axes == ("local",)
    assert out[3].axes == ("data", "seq", "model")


def test_assert_collectives_strict_bytes_and_forbid():
    inv = {("all-reduce", ("data",)): (3, 1000),
           ("collective-permute", ("seq",)): (2, 64)}
    # min_bytes honoured
    ca.assert_collectives(inv, [(("all-reduce",), "data", 900)])
    with pytest.raises(AssertionError, match="bytes"):
        ca.assert_collectives(inv, [(("all-reduce",), "data", 2000)])
    # forbid rejects a misrouted collective
    with pytest.raises(AssertionError, match="forbidden"):
        ca.assert_collectives(inv, [], forbid=[
            (("collective-permute",), "seq")])
    # any unattributed row fails the audit unconditionally
    bad = dict(inv)
    bad[("collective-permute", ("?",))] = (97, 12345)
    with pytest.raises(AssertionError, match="unattributed"):
        ca.assert_collectives(bad, [(("all-reduce",), "data")])


def test_audit_rejects_misrouted_ring_layout():
    """End-to-end misroute detection: ring attention deliberately run
    over the WRONG mesh axis compiles to permutes on that axis; the
    audit asserting 'permutes must ride seq, none may ride data'
    rejects the layout."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.context_parallel import (
        sequence_parallel_attention)

    mesh = make_mesh((2, 2), ("seq", "data"), devices=jax.devices()[:4])
    B, H, S, D = 2, 2, 32, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)

    def misrouted(q, k, v):
        return sequence_parallel_attention(q, k, v, mesh, axis="data",
                                           impl="ring", causal=True)

    hlo = jax.jit(misrouted).lower(q, q, q).compile().as_text()
    inv = ca.inventory(hlo, mesh)
    with pytest.raises(AssertionError):
        ca.assert_collectives(
            inv, [(("collective-permute",), "seq")],
            forbid=[(("collective-permute",), "data")])


def test_assert_collectives_accepts_merged_axes_and_fails_on_missing():
    inv = {("all-reduce", ("data", "seq")): (3, 1000),
           ("collective-permute", ("pipe",)): (2, 64)}
    ca.assert_collectives(inv, [(("all-reduce",), "data"),
                                (("collective-permute",), "pipe")])
    with pytest.raises(AssertionError, match="model"):
        ca.assert_collectives(inv, [(("all-reduce",), "model")])


def test_dp_tp_training_program_has_expected_collectives():
    """End-to-end: a DP x TP trained MLP on an 8-virtual-device mesh
    must compile to a gradient all-reduce touching 'data' and a TP
    collective touching 'model'."""
    import jax
    from jax.sharding import PartitionSpec as P
    from paddle_tpu import layers
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.executor import (ParallelExecutor,
                                              ShardingSpec)

    mesh = make_mesh((4, 2), ("data", "model"),
                     devices=jax.devices()[:8])
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [32], dtype="float32")
        label = layers.data("label", [1], dtype="int32")
        h = layers.fc(x, size=64, act="relu", name="tp_fc1")
        logits = layers.fc(h, size=8, name="tp_fc2")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            logits, label))
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    specs = {p.name: P(None, "model") for p in main.all_parameters()
             if len(p.shape or ()) == 2 and (p.shape or [0])[-1] % 2 == 0
             and (p.shape or [0])[-1] >= 64}
    exe = ParallelExecutor(mesh=mesh,
                           sharding=ShardingSpec(specs=specs,
                                                 feed_axis="data"))
    pt.Executor().run(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 32).astype(np.float32),
            "label": rng.randint(0, 8, (16, 1)).astype(np.int32)}
    (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(float(np.asarray(lv)))

    hlo = ca.compiled_hlo_for(exe, main)
    inv = ca.inventory(hlo, mesh)
    assert inv, "no collectives found in a DPxTP program"
    ca.assert_collectives(inv, [
        (("all-reduce", "reduce-scatter"), "data"),
        (("all-reduce", "reduce-scatter", "all-gather"), "model"),
    ])
    # est bytes are positive for the gradient sync
    data_bytes = sum(b for (k, axes), (_c, b) in inv.items()
                     if "data" in axes and k == "all-reduce")
    assert data_bytes > 0


@pytest.mark.parametrize("impl,expect_kind", [
    ("ring", "collective-permute"),
    ("ulysses", "all-to-all"),
])
def test_sequence_parallel_attention_collectives(impl, expect_kind):
    """The two context-parallel schemes compile to their signature
    collectives over the 'seq' axis: ring -> neighbor
    collective-permute, Ulysses -> head/seq all-to-all."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.context_parallel import (
        sequence_parallel_attention)

    mesh = make_mesh((4,), ("seq",), devices=jax.devices()[:4])
    B, H, S, D = 2, 4, 64, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)

    def fn(q, k, v):
        return sequence_parallel_attention(q, k, v, mesh, axis="seq",
                                           impl=impl, causal=True)

    hlo = jax.jit(fn).lower(q, q, q).compile().as_text()
    inv = ca.inventory(hlo, mesh)
    ca.assert_collectives(inv, [((expect_kind,), "seq")])
