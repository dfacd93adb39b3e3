"""The main path's Pallas kernels compile for a TPU v5e — without one.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). These are
the kernels chip_smoke.py's train and kernel phases reach, at its
shapes: what Mosaic refuses (an unaligned slice, too much VMEM) fails
here at no chip time. Nothing runs, so this says nothing about results
— chip_smoke.py compares those on the chip. A compile that passes here
is not a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports every test file. Keep all such tests in THIS file — a
second file can land on another worker, where the fixture would skip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.fused_gru import fused_gru
from paddle_tpu.ops.pallas.fused_lstm import fused_lstm


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels_in(fn, *shapes):
    """Compile for the described chip; count the Mosaic kernels."""
    return jax.jit(fn).lower(*shapes).compile().as_text().count(
        "tpu_custom_call")


def _sum_f32(outs):
    return sum(o.astype(jnp.float32).sum()
               for o in jax.tree_util.tree_leaves(outs))


def _site_counts(family, label=0):
    """Counter of one label (by default the first, path) of a site
    counter family, summed over the others."""
    import collections

    from paddle_tpu.observability import default_registry
    fam = default_registry().get(family)
    counts = collections.Counter()
    for labels, child in (fam.samples() if fam is not None else ()):
        counts[labels[label]] += child.value
    return counts


def _sites(which, label=0):
    """Counter of path (or of label 4, rows_a_block): flash `fwd` or
    `bwd` calls traced so far."""
    return _site_counts(f"paddle_tpu_flash_{which}_sites_total", label)


# [B, H, S, D] of chip_smoke.py's train step (b4 x s2048, 8 heads of
# 64), and a length that is not a multiple of 128: the tiles shrink to
# it in whole 128-lane columns, which interpret mode never checks
# against Mosaic's (8, 128) tiling. Then train-ep32's site (chipbench/
# configs/joyai-llm-flash.json: 1 x 32 x 4096, 192-wide keys, 128-wide
# values, causal, no bias), whose resident K and V with their f32
# accumulators pass the default scoped VMEM: the call asks for what it
# counted. Then serve-chat's two prefill sites (decoder-lm-base: f32, 8
# heads of 64, causal, the 512 and 2048 prompt buckets).
@pytest.mark.parametrize("shape,bias,dtype", [
    ((4, 8, s, 64, 64), m, jnp.bfloat16) for s in (2048, 1100)
    for m in ("causal", "pad_row", "causal_pad_row", "dense")
] + [((1, 32, 4096, 192, 128), "causal", jnp.bfloat16)] + [
    ((1, 8, s, 64, 64), "causal", jnp.float32) for s in (512, 2048)],
    ids=lambda v: v if isinstance(v, str) else (
        "x".join(map(str, v)) if isinstance(v, tuple) else v.__name__))
def test_flash_attention_fwd_bwd_compiles(one_chip, shape, bias, dtype):
    b, h, seq, d, d_v = shape
    q = jax.ShapeDtypeStruct((b, h, seq, d), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, h, seq, d_v), dtype, sharding=one_chip)
    # the masks models/transformer.py builds: a [B,1,1,S] pad-row mask
    # (encoder and cross attention), the same with causal=True (decoder
    # self-attention); a [B,1,S,S] bias is what an op handed a dense
    # mask still reaches the kernels with
    mshape = {"causal": None, "pad_row": (b, 1, 1, seq),
              "causal_pad_row": (b, 1, 1, seq),
              "dense": (b, 1, seq, seq)}[bias]

    def loss(q, k, v, m=None):
        return _sum_f32(flash_attention(q, k, v, m,
                                        causal=bias.startswith("causal"),
                                        interpret=False))

    args = (q, q, v) if mshape is None else (
        q, q, v, jax.ShapeDtypeStruct(mshape, jnp.float32,
                                      sharding=one_chip))
    fwd, bwd = _sites("fwd"), _sites("bwd")
    # forward + the one backward kernel, a head's K and V resident
    assert _kernels_in(jax.grad(loss, argnums=(0, 1, 2)), *args) == 2
    assert _sites("fwd") - fwd == {"resident": 1}
    assert _sites("bwd") - bwd == {"resident": 1}


# laguna-xs2.train-s8192's two kinds of site (chipbench/configs/
# laguna-xs2.json: 1 x 8192, 8 key heads of 128): 48 query heads, causal;
# 64 query heads under a window of 512. Then a window and a sequence that
# are no multiple of the tile, which interpret mode never holds against
# Mosaic's tiling.
@pytest.mark.parametrize("heads,seq,window", [
    (48, 8192, None), (64, 8192, 512), (64, 4096, 512), (16, 1100, 300)])
def test_grouped_and_windowed_flash_sites_compile(one_chip, heads, seq,
                                                  window):
    import re
    q = jax.ShapeDtypeStruct((1, heads, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, seq, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return _sum_f32(flash_attention(q, k, v, causal=True,
                                        window=window, interpret=False))

    fwd, bwd = _sites("fwd"), _sites("bwd")
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    calls = re.findall(
        r"%\S*?(flash_[a-z_]*?)_*\.?\d* = [^\n]*tpu_custom_call", text)
    suffix = "_window" if window else ""
    assert sorted(calls) == ["flash_bwd_dkv_dq" + suffix,
                             "flash_fwd" + suffix]
    # a head's K and V resident in both passes at 8192 keys; dK and dV
    # leave the kernel at the 8 key heads, summed over the group
    assert _sites("fwd") - fwd == {"resident": 1}
    assert _sites("bwd") - bwd == {"resident": 1}


# The same sites sequence-major (layout="bshd", [B, S, H, D]): train-s2048's
# three kinds (two 64-wide heads a 128-lane block, under a key-row mask),
# the mesh cell's shard (4 heads: two blocks), a length that is no
# multiple of 128, four 32-wide heads a block, and 128-wide heads under
# grouped key heads (a head a block). What interpret mode never holds
# against Mosaic: the per-head row and sublane slices of the scratch, the
# 128-lane blocks of a [B, S, H * D] view, the turn of a two-head
# accumulator.
@pytest.mark.parametrize("shape,hk,causal,masked,heads", [
    ((8, 2048, 8, 64), 8, False, True, 2),
    ((8, 2048, 8, 64), 8, True, True, 2),
    ((8, 2048, 4, 64), 4, True, True, 2),
    ((4, 1100, 8, 64), 8, True, True, 2),
    ((2, 2048, 16, 32), 16, True, False, 4),
    ((1, 4096, 16, 128), 4, True, False, 1),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_sequence_major_flash_sites_compile(one_chip, shape, hk, causal,
                                            masked, heads):
    import collections

    from paddle_tpu.observability import default_registry
    b, seq, h, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, seq, hk, d), jnp.bfloat16,
                              sharding=one_chip)
    args = (q, kv, kv)
    if masked:
        args += (jax.ShapeDtypeStruct((b, 1, 1, seq), jnp.float32,
                                      sharding=one_chip),)

    def loss(q, k, v, m=None):
        return _sum_f32(flash_attention(q, k, v, m, causal=causal,
                                        interpret=False, layout="bshd"))

    def by_block():     # (path, heads a block), over windows and groups
        fam = default_registry().get("paddle_tpu_flash_bwd_sites_total")
        counts = collections.Counter()
        for labels, child in (fam.samples() if fam is not None else ()):
            counts[labels[0], labels[3]] += child.value
        return counts

    before = by_block()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert by_block() - before == {("resident", str(heads)): 1}
    # q, k, v, o and their gradients are read and written where they
    # lie: XLA lays no head-major copy beside the calls
    assert f"bf16[{b},{h},{seq},{d}]" not in text
    assert " transpose(" not in text


def test_flash_backward_beyond_the_vmem_budget_compiles(one_chip):
    """A head whose K and V with their accumulators pass the budget
    (32,768 keys of 128: 100 MB) is walked a segment at a time, dQ an
    f32 partial a segment: the same kernel, and it compiles. The
    forward holds no accumulator a key: its count keeps these K and V
    resident (34 MB double-buffered + a tile in flight)."""
    q = jax.ShapeDtypeStruct((1, 2, 32768, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return _sum_f32(flash_attention(q, k, v, causal=True,
                                        interpret=False))

    fwd, bwd = _sites("fwd"), _sites("bwd")
    assert _kernels_in(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == 2
    assert _sites("fwd") - fwd == {"resident": 1}
    assert _sites("bwd") - bwd == {"partial": 1}


@pytest.mark.parametrize("seq,budget", [(32768, 16 << 20), (65536, None)],
                         ids=["32768_of_a_16MiB_budget", "65536"])
def test_flash_forward_beyond_the_vmem_budget_compiles(one_chip, seq, budget,
                                                       monkeypatch):
    """A head whose K and V pass the forward's budget is walked a segment
    a grid step, the statistics' rows and the accumulator waiting in
    scratch between them: the same kernel, it compiles, and the
    logsumexp still leaves compact. 65,536 keys of 128 pass the 64 MiB
    as they are; 32,768 (ISSUE 40's shape, which the count keeps
    resident) pass a budget of 16."""
    import importlib
    import re
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    if budget is not None:
        monkeypatch.setattr(fa, "_VMEM_BUDGET", budget)
    q = jax.ShapeDtypeStruct((1, 2, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    fwd = _sites("fwd")
    text = jax.jit(lambda q, k, v: fa._fwd(
        q, k, v, None, 128 ** -0.5, True, None, None, None, False,
        False)).lower(
        q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert _sites("fwd") - fwd == {"partial": 1}
    assert not re.findall(rf"f32\[1,2,{seq},128\]", text)


@pytest.mark.parametrize("batch,seq,rows", [(8, 2048, 1), (64, 256, 4)],
                         ids=["8x2048", "64x256"])
def test_train_step_holds_18_forward_and_18_backward_kernels_and_no_score_sized_mask(
        one_chip, monkeypatch, batch, seq, rows):
    """The step of transformer-base.train-s2048 (chipbench/configs,
    batch 8 x sequence 2048, AMP bf16), compiled whole for the described
    chip: 18 attention sites x (forward, one backward kernel that forms
    each score tile once: ISSUE 38; the dq / dkv pair made it 54), every
    backward with its head's K and V resident, and causality reaches
    them as a flag, so nothing of [.., 2048, 2048] f32 is an operand or
    a constant (ISSUE 32; until then the decoder's self-attention was
    handed an f32[8,1,2048,2048] sum of triangle and pad mask).

    And the step of transformer-base.train-s256 (batch 64 x 256: ISSUE
    51), whose sites the dispatcher — left the choice, as on the chip —
    hands the same two kernels since the crossover stands at 256, under
    the short-sequence plan (`rows` batch rows a grid step): no site is
    composed and no [64, 8, 256, 256] score tensor exists in the step."""
    import importlib
    import re

    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    vocab = 32000
    sites = _sites("bwd")
    sdpa = _site_counts("paddle_tpu_sdpa_sites_total")
    fwd_rows, bwd_rows = _sites("fwd", 4), _sites("bwd", 4)
    # the rule asks jax.default_backend(), which is still the CPU here:
    # the knob as a TPU reads its default, so that the crossover decides
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas"),
        "pallas_dispatch", lambda knob, default: (True, False))
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas.flash_attention"),
        "_interpret_default", lambda: False)
    pt.reset_default_programs()
    pt.reset_global_scope()
    try:
        with pt.amp.amp_guard(True):
            main, startup, fetch = transformer.build_train(
                src_vocab=vocab, trg_vocab=vocab, max_len=seq, n_layer=6,
                n_head=8, d_model=512, d_inner=2048)
            exe = pt.Executor()
            exe.run(startup)
            scope = pt.global_scope()
            step = exe._compile(main.desc, main.desc.block(0), None,
                                [fetch["loss"].name], scope)

            def sds(shape, dtype):
                return jax.ShapeDtypeStruct(shape, dtype,
                                            sharding=one_chip)

            def state(names):
                return {n: sds(scope.get(n).shape, scope.get(n).dtype)
                        for n in names}

            feed = {n: sds((batch, seq, 1), jnp.int32)
                    for n in ("src_ids", "trg_ids", "trg_labels")}
            feed["pos_ids"] = sds((seq,), jnp.int32)
            fwd_sites = _sites("fwd")
            text = step.jitted.lower(
                feed, state(step.ro_names), state(step.rw_names),
                sds((), jnp.int32)).compile().as_text()
    finally:
        pt.reset_global_scope()       # a gigabyte of weights and moments
    assert text.count("tpu_custom_call") == 36
    calls = re.findall(
        r"%\S*(flash_[a-z_]*?)_*\.\d+ = [^\n]*tpu_custom_call", text)
    assert sorted(set(calls)) == ["flash_bwd_dkv_dq", "flash_fwd"]
    assert calls.count("flash_fwd") == 18
    assert _sites("bwd") - sites == {"resident": 18}
    assert _sites("fwd") - fwd_sites == {"resident": 18}
    # every site was handed the kernels (the build's shape inference
    # counts no site), each call with the plan's batch rows a grid step
    # (a grad op applies its forward op's pullback and is no site)
    assert _site_counts("paddle_tpu_sdpa_sites_total") - sdpa == {
        "flash": 18}
    assert _sites("bwd", 4) - bwd_rows == {str(rows): 18}
    assert _sites("fwd", 4) - fwd_rows == {str(rows): 18}
    assert f"[{batch},8,{seq},{seq}]" not in text
    # the logsumexp leaves the forward [8, 8, 2048] f32 (ISSUE 40; until
    # then 128 lanes wide, 67 MB a site, for XLA to cut a column out of)
    assert not re.findall(rf"f32\[{batch},8,{seq},128\]", text)
    # the sites read q, k, v and write o sequence-major (ISSUE 47): no
    # head-major [8, 8, 2048, 64] array is laid out anywhere in the step
    assert not re.findall(rf"bf16\[{batch},8,{seq},64\]", text)
    score_sized = re.findall(
        rf"f32\[(?:\d+,)*{seq},{seq}\]", text)
    # [8, 2048, d_inner = 2048] activations are the only such shape
    assert set(score_sized) <= {f"f32[{batch},{seq},{seq}]"}, \
        sorted(set(score_sized))


# the stacked-LSTM LM's shapes (T=64, B=64, H=512; chip_smoke.py's rnn): fused_lstm and
# fused_gru are ON by default on a TPU (ops/sequence_ops.py), so every
# LSTM/GRU user reaches them; bf16 is what AMP hands them.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_rnn_fwd_bwd_compiles(one_chip, cell, dtype):
    t, b, h = 64, 64, 512
    gates = 4 if cell == "lstm" else 3

    def sds(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if cell == "lstm":      # x, w, b, h0, c0
        kernel, args = fused_lstm, (
            sds(t, b, gates * h), sds(h, gates * h), sds(gates * h),
            sds(b, h), sds(b, h))
    else:                   # x, w, h0
        kernel, args = fused_gru, (
            sds(t, b, gates * h), sds(h, gates * h), sds(b, h))

    def loss(*a):           # a = (*args, lengths); interpret=False
        return _sum_f32(kernel(*a, False))

    # forward + backward: one kernel each
    assert _kernels_in(jax.grad(loss, argnums=tuple(range(len(args)))),
                       *args, sds(b, dt=jnp.int32)) == 2


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_flash_attention_under_a_mesh_compiles_per_shard(topo, layout):
    """GSPMD cannot partition a Mosaic kernel: lowering the bare kernel
    with mesh-sharded operands raises "Mosaic kernels cannot be
    automatically partitioned" (what ParallelExecutor hit at S >= 512
    before PR 22). ops/nn_ops.py runs it per shard instead — of batch
    and heads, wherever the layout holds the heads (train-mesh-dp2tp2's
    sites are sequence-major since PR 47)."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.nn_ops import _per_shard_attention

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    head_dim = 1 if layout == "bhsd" else 2
    shape, spec = [16, 2048, 2048, 64], ["data", None, None, None]
    shape[head_dim], spec[head_dim] = 8, "model"
    q = jax.ShapeDtypeStruct(tuple(shape), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(*spec)))
    m = jax.ShapeDtypeStruct((16, 1, 1, 2048), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    attend = functools.partial(flash_attention, causal=True,
                               interpret=False, layout=layout)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(attend).lower(q, q, q, m)
    sharded = functools.partial(_per_shard_attention, attend, mesh,
                                batch_axis="data", head_axis="model",
                                head_dim=head_dim)
    text = jax.jit(sharded).lower(q, q, q, m).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    # each device attends its own shard of 8 rows and 4 heads: no
    # collective
    assert "all-gather" not in text and "all-reduce" not in text
    # and differentiates it there: the forward and the one backward
    # kernel a shard, what train-mesh-dp2tp2 runs 18 times a step
    text = jax.jit(jax.grad(
        lambda q, k, v, m: _sum_f32(sharded(q, k, v, m)),
        argnums=(0, 1, 2))).lower(q, q, q, m).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "all-gather" not in text and "all-reduce" not in text
    assert " transpose(" not in text


def test_a_mesh_layer_pair_all_reduces_one_tensor_a_shared_input(
        topo, monkeypatch):
    """ONE encoder and ONE decoder layer of transformer-base.train-mesh-
    dp2tp2's step (batch 16 x 2048 on ('data','model') = (2,2), AMP
    bf16, tp_param_specs; a 1,024-row vocabulary: the head is not the
    subject), compiled for the described v5e:2x2 through
    ParallelExecutor._compile with the executor's own arg_shardings on
    ShapeDtypeStructs — the recipe for the whole cell (6 + 6 layers, the
    32k vocabulary, ~60 s: PERF.md section 7). Over 'model' the step
    all-reduces 11 tensors of bf16[16384,512] — 2 + 2 an encoder layer,
    3 + 4 a decoder layer; 16 before ISSUE 50, when each of q, k, v
    (and of cross-attention's k, v) brought its input gradient across
    by itself — and nothing else activation-sized crosses it: the
    stacked contraction of ops/math_ops.py fanout_mul reshards
    nothing."""
    import importlib

    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import collective_audit as ca
    from paddle_tpu.parallel.executor import ParallelExecutor, ShardingSpec

    batch, seq, vocab = 16, 2048, 1024
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", "force")
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas.flash_attention"),
        "_interpret_default", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    pt.reset_default_programs()
    pt.reset_global_scope()
    try:
        with pt.amp.amp_guard(True):
            main, startup, fetch = transformer.build_train(
                src_vocab=vocab, trg_vocab=vocab, max_len=seq, n_layer=1,
                n_head=8, d_model=512, d_inner=2048)
            pt.Executor().run(startup)
            scope = pt.global_scope()
            sharding = ShardingSpec(specs=transformer.tp_param_specs(main),
                                    feed_axis="data")
            sharding.specs["pos_ids"] = P()
            ids = ((batch, seq, 1), "int64")
            sig = tuple(sorted(
                [(n, ids) for n in ("src_ids", "trg_ids", "trg_labels")]
                + [("pos_ids", ((seq,), "int64"))]))
            step = ParallelExecutor(mesh=mesh, sharding=sharding)._compile(
                main.desc, main.desc.block(0), sig, [fetch["loss"].name],
                scope)
            feeds, ro, rw, step_sh = step.arg_shardings

            def state(names, shardings):
                return [jax.ShapeDtypeStruct(
                    scope.get(n).shape, scope.get(n).dtype, sharding=sh)
                    for n, sh in zip(names, shardings)]

            text = step.jitted.lower(
                [jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)
                 for (_, (shape, _)), sh in zip(sig, feeds)],
                state(step.ro_names, ro), state(step.rw_names, rw),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=step_sh)
            ).compile().as_text()
    finally:
        pt.reset_global_scope()
    a_shard = batch // 2 * seq * 512 // 2   # of a column-parallel output
    assert ca.tensors_over(text, mesh, "model", min_elements=a_shard) == {
        ("all-reduce", "bf16[16384,512]"): 11}
    assert "all-reduce-start" not in text           # all synchronous
    ca.assert_collectives(ca.inventory(text, mesh),
                          [(("all-reduce",), "data")])
    assert text.count("tpu_custom_call") == 6


# serve-chat's caches (128 slots x 8 heads x 2048 positions, chipbench/
# configs/decoder-lm-base.json) and the shapes the next serving
# configuration may store: d_key 128, bf16. A v5e holds a d_key of 64
# with the POSITIONS on its lanes, a d_key of 128 row-major; the op's
# rule reads that from the backend (ops/cache_ops.py device_lane_axis)
# and hands the kernel the cache in the device's own order, where the
# transposes around the call are bitcasts. Handed the other order, XLA
# copies the whole cache in and out (1 GB of temporaries at f32 d64).
@pytest.mark.parametrize("slots", [128, 4])
@pytest.mark.parametrize("d_key,lane_axis", [(64, 2), (128, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_cache_append_compiles_in_place(topo, one_chip, dtype, d_key,
                                           lane_axis, slots):
    from paddle_tpu.ops.cache_ops import device_lane_axis
    from paddle_tpu.ops.pallas.kv_cache_append import kv_cache_append
    shape = (slots, 8, 2048, d_key)
    assert device_lane_axis(shape, dtype, topo.devices[0]) == lane_axis
    cache = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    new = jax.ShapeDtypeStruct((slots, 8, 1, d_key), dtype,
                               sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda c, n, p: kv_cache_append(c, n, p, lane_axis=lane_axis,
                                        interpret=False),
        donate_argnums=0).lower(cache, new, pos).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " while(" not in text
    assert "may-alias" in text[:text.find("\n\n")] or \
        "must-alias" in text[:text.find("\n\n")]
    # nothing of the cache's size beside the cache: a copy would show
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


# serve-chat's decode step (chipbench/configs/decoder-lm-base.json: 128
# slots, 6 layers, 8 heads of 64, 2048 positions, f32), both cache
# buckets, compiled whole for the described chip. The appends and the
# attention read the SAME cache buffers: the append kernel hands its
# output back in the model's axis order, the attention kernel takes the
# device's order again, and XLA must make nothing of the two transposes.
@pytest.mark.parametrize("bucket", [512, 2048])
@pytest.mark.parametrize("whole_step", [False, True],
                         ids=["kernel_alone", "whole_step"])
def test_decode_step_reads_its_caches_in_place(topo, one_chip,
                                               monkeypatch, whole_step,
                                               bucket):
    """12 appends + 6 length-bounded attention reads as custom calls,
    every cache aliased to its output, nothing cache-sized copied, no
    loop over slots, and no multiply-reduce fusion over a cache (ISSUE
    35; until then twelve of them read all 6.44 GB a step). And the
    same of the one kernel alone, where a fault is cheaper to read."""
    import re

    from paddle_tpu.ops import cache_ops
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    slots, heads, max_seq, d_key = 128, 8, 2048, 64
    shape = (slots, heads, max_seq, d_key)
    cache_re = r"f32\[%d,%d,(?:%d,%d|%d,%d)\]" % (
        slots, heads, max_seq, d_key, d_key, max_seq)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def checks(compiled, calls, caches):
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == calls
        assert not re.findall(r"= \S.* while\(", text)
        assert not re.findall("= " + cache_re + r"\S* copy\(", text)
        # XLA's own temporaries: activations and the work list, not a
        # 537-MB cache (a copy of one would show here first)
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
        entry = text[text.find("\nENTRY "):]
        params = {int(n) for n in re.findall(
            cache_re + r"[^\n]*? parameter\((\d+)\)", entry)}
        assert len(params) == caches
        return text, params

    if not whole_step:
        compiled = jax.jit(
            lambda q, k, v, n: decode_attention(q, k, v, n, bound=bucket,
                                                interpret=False)).lower(
            sds((slots, heads, 1, d_key), jnp.float32),
            sds(shape, jnp.float32), sds(shape, jnp.float32),
            sds((slots,), jnp.int32)).compile()
        checks(compiled, 1, 2)
        return

    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    # the rules ask the backend, which is still the CPU here, how it
    # holds a cache: answer for the described chip
    real = cache_ops.device_lane_axis
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        cache_ops, "device_lane_axis",
        lambda shape, dtype: real(shape, dtype, topo.devices[0]))
    lm = transformer._build_lm_program(
        "decode", bucket, 32000, max_seq, slots, 6, heads, 512, 2048, 0)
    block = lm.main.desc.block(0)

    class EveryVarThere:         # shapes come from the program, not
        def has(self, name):     # from 6.44 GB of caches in a scope
            return True

    step = pt.Executor()._compile(lm.main.desc, block, None,
                                  [lm.fetch_name], EveryVarThere())

    def state(names):
        return {n: sds(block.find_var_recursive(n).shape, jnp.float32)
                for n in names}

    feed = {"token_ids": sds((slots, 1, 1), jnp.int32),
            "positions": sds((slots,), jnp.int32),
            "lengths": sds((slots,), jnp.int32)}
    compiled = step.jitted.lower(
        feed, state(step.ro_names), state(step.rw_names),
        sds((), jnp.int32)).compile()
    text, cache_params = checks(compiled, 12 + 6, 12)
    assert len(step.rw_names) == 12
    header = text[:text.find("\n\n")]
    aliased = {int(m) for m in re.findall(
        r"\(\s*(\d+)\s*,\s*\{[^}]*\}\s*,\s*(?:may|must)-alias\)", header)}
    assert cache_params <= aliased
    # what is left of that name reduces [128] rows of a layer norm
    over_a_cache = [ln for ln in text.splitlines()
                    if "multiply_reduce" in ln and " fusion(" in ln
                    and re.search(cache_re, ln)]
    assert not over_a_cache, over_a_cache[:2]


def test_windowed_kernels_map_to_their_attention_op_by_role(one_chip,
                                                            monkeypatch):
    """A two-layer grouped-query step (a window layer, a full layer) at
    128-wide heads, compiled whole for the described chip: one forward
    and one backward kernel a site, the window layer's named
    ``flash_*_window``, and the op table (core/op_table.py) charges
    each to its attention op — forward to the op, backward to its grad
    op — so the readers of device time by role count them."""
    import collections
    import importlib

    import paddle_tpu as pt
    from paddle_tpu.core import op_table
    from paddle_tpu.models import decoder_moe

    seq = 1024
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", "force")
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas.flash_attention"),
        "_interpret_default", lambda: False)
    pt.reset_default_programs()
    pt.reset_global_scope()
    rope = {"rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1}
    try:
        with pt.amp.amp_guard(True):
            main, startup, fetch = decoder_moe.build_train(
                attention="gqa", topk_method="greedy", trg_vocab=512,
                max_len=seq, hidden_size=256, intermediate_size=512,
                moe_intermediate_size=128, n_routed_experts=8,
                experts_held=2, num_experts_per_tok=2,
                num_hidden_layers=2, num_nextn_predict_layers=0,
                layer_types=["sliding_attention", "full_attention"],
                num_attention_heads_per_layer=[4, 6],
                mlp_layer_types=["dense", "sparse"],
                num_key_value_heads=2, head_dim=128, sliding_window=512,
                rope_parameters={"full_attention": rope,
                                 "sliding_attention": rope}, gating=True)
            exe = pt.Executor()
            exe.run(startup)
            scope = pt.global_scope()
            step = exe._compile(main.desc, main.desc.block(0), None,
                                [fetch["loss"].name], scope)

            def sds(shape, dtype):
                return jax.ShapeDtypeStruct(shape, dtype,
                                            sharding=one_chip)

            def state(names):
                return {n: sds(scope.get(n).shape, scope.get(n).dtype)
                        for n in names}

            feed = {n: sds((1, seq, 1), jnp.int32)
                    for n in ("src_ids", "trg_ids", "trg_labels")}
            feed["pos_ids"] = sds((seq,), jnp.int32)
            text = step.jitted.lower(
                feed, state(step.ro_names), state(step.rw_names),
                sds((), jnp.int32)).compile().as_text()
    finally:
        pt.reset_global_scope()
    charged = collections.Counter(
        (name.rstrip("0123456789._").replace("jvp_", ""), ref.op_type,
         ref.role)
        for name, ref in op_table.parse(text).ops.items()
        if "flash_" in name)
    attn = "scaled_dot_product_attention"
    assert charged == {
        ("flash_fwd_window", attn, "forward"): 1,
        ("flash_bwd_dkv_dq_window", "__vjp__." + attn, "backward"): 1,
        ("flash_fwd", attn, "forward"): 1,
        ("flash_bwd_dkv_dq", "__vjp__." + attn, "backward"): 1}


def test_a_looped_step_compiles_with_its_flash_calls_inside_the_while(
        one_chip, monkeypatch):
    """A two-layer, four-pass step of models/looped_lm.py at 128-wide
    heads, compiled whole for the described chip: the loop is ONE
    ``while`` each way, the two attention sites' kernels are compiled
    once each way INSIDE its body (2 + 2 custom calls, not 8 + 8), under
    the names the trace readers match, and the op table charges the
    backward kernel, which the loop's grad op emitted, to the sub-block's
    attention op as its grad."""
    import collections
    import importlib
    import re

    import paddle_tpu as pt
    from paddle_tpu.core import op_table
    from paddle_tpu.models import looped_lm

    seq = 1024
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", "force")
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas.flash_attention"),
        "_interpret_default", lambda: False)
    pt.reset_default_programs()
    pt.reset_global_scope()
    try:
        with pt.amp.amp_guard(True):
            main, startup, fetch = looped_lm.build_train(
                trg_vocab=512, max_len=seq, hidden_size=256,
                intermediate_size=512, num_hidden_layers=2,
                num_attention_heads=2, num_key_value_heads=2,
                head_dim=128, total_ut_steps=4)
            exe = pt.Executor()
            exe.run(startup)
            scope = pt.global_scope()
            step = exe._compile(main.desc, main.desc.block(0), None,
                                [fetch["loss"].name], scope)

            def sds(shape, dtype):
                return jax.ShapeDtypeStruct(shape, dtype,
                                            sharding=one_chip)

            def state(names):
                return {n: sds(scope.get(n).shape, scope.get(n).dtype)
                        for n in names}

            feed = {n: sds((1, seq, 1), jnp.int32)
                    for n in ("src_ids", "trg_ids", "trg_labels")}
            feed["pos_ids"] = sds((seq,), jnp.int32)
            text = step.jitted.lower(
                feed, state(step.ro_names), state(step.rw_names),
                sds((), jnp.int32)).compile().as_text()
    finally:
        pt.reset_global_scope()
    assert text.count("tpu_custom_call") == 4
    bodies = set(re.findall(r"\bwhile\([^\n]*body=%?([^\s,)}]+)", text))
    assert len(bodies) == 2           # the loop, and its transpose
    inside, current = collections.Counter(), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{$", line)
        if head:
            current = head.group(1)
        elif "tpu_custom_call" in line:
            inside[current in bodies] += 1
    assert inside == {True: 4}
    charged = collections.Counter(
        (name.rstrip("0123456789._").replace("jvp_", ""), ref.op_type,
         ref.role, len(ref.block_path))
        for name, ref in op_table.parse(text).ops.items()
        if "flash_" in name)
    attn = "scaled_dot_product_attention"
    assert charged == {
        ("flash_fwd", attn, "forward", 2): 2,
        ("flash_bwd_dkv_dq", "__vjp__." + attn, "backward", 2): 2}


# The hybrid family's decode step (models/hybrid_ssm.py, granite-4.0-h-micro's
# widths: 64 slots, 64 heads x 64 with a 128-wide state; 32 query heads
# over 8 key heads of 64, bfloat16 caches to 4096 positions).
def test_ssm_state_update_compiles_in_place(one_chip):
    """One custom call, the 134-MB state aliased to its output, nothing
    state-sized copied or planned as a temporary."""
    import re

    from paddle_tpu.ops.pallas.ssm_state_update import ssm_state_update

    slots, d_state, columns = 64, 128, 4096

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda s, a, dx, b, c: ssm_state_update(s, a, dx, b, c,
                                                interpret=False),
        donate_argnums=0).lower(
        sds(slots, d_state, columns), sds(slots, columns),
        sds(slots, columns), sds(slots, d_state),
        sds(slots, d_state)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"%ssm_state_update\S* = ", text)   # named for the trace
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slots * d_state * columns * 4
    assert memory.temp_size_in_bytes < 8 << 20
    assert not re.findall(r"= f32\[64,128,4096\]\S* copy\(", text)


@pytest.mark.parametrize("bound", [1024, 4096])
def test_decode_attention_at_four_query_heads_a_key_head_compiles(
        topo, one_chip, bound):
    from paddle_tpu.ops.cache_ops import device_lane_axis
    from paddle_tpu.ops.pallas.decode_attention import (decode_attention,
                                                        fits)

    shape = (64, 8, 4096, 64)
    # a bfloat16 cache of 64-wide heads is held with its positions on
    # the lanes too: the kernel serves it
    assert device_lane_axis(shape, jnp.bfloat16, topo.devices[0]) == 2
    assert fits(shape, jnp.bfloat16, 2, bound)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, n: decode_attention(q, k, v, n, bound=bound,
                                            interpret=False)).lower(
        sds((64, 32, 1, 64), jnp.bfloat16), sds(shape, jnp.bfloat16),
        sds(shape, jnp.bfloat16), sds((64,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# The zaya1-8b.serve-reasoning cell's decode sites (chipbench/configs/
# zaya1-8b.json: 96 slots, 8 query heads over 2 key heads of 128,
# bfloat16 caches to 2048 positions, cache buckets 1024 and 2048). A
# 128-wide key is held row-major, and the kernel's row-major body takes
# the caches as they lie: blocks (2, 256, 128), no transposed view.
@pytest.mark.parametrize("bound", [1024, 2048])
def test_row_major_decode_attention_compiles_at_the_cells_shape(
        topo, one_chip, bound):
    import re

    from paddle_tpu.ops.cache_ops import device_lane_axis
    from paddle_tpu.ops.pallas.decode_attention import (decode_attention,
                                                        fits)

    slots, key_heads, group, max_seq, d_key = 96, 2, 4, 2048, 128
    shape = (slots, key_heads, max_seq, d_key)
    assert device_lane_axis(shape, jnp.bfloat16, topo.devices[0]) == 3
    assert fits(shape, jnp.bfloat16, 3, bound)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, n: decode_attention(q, k, v, n, bound=bound,
                                            lane_axis=3, interpret=False)
    ).lower(sds((slots, key_heads * group, 1, d_key), jnp.bfloat16),
            sds(shape, jnp.bfloat16), sds(shape, jnp.bfloat16),
            sds((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"%decode_attention_row_major\S* = ", text)
    assert not re.findall(r"= \S.* while\(", text)
    # the caches go in as they are: nothing cache-shaped is copied or
    # sliced to the bound (one slot's slice to 1024 positions is 0.5 MB,
    # a cache's 50 MB), the work list and the queries are all XLA holds
    assert not re.findall(r"= bf16\[%d,%d,\d{3,},%d\]\S* (?:copy|slice)\("
                          % (slots, key_heads, d_key), text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# The CCA / expert family's programs (models/cca_moe.py) at ZAYA1-8B's
# published widths and the zaya1-8b.serve-reasoning cell's sizes: 96
# slots, 8 query heads over 2 key heads of 128, bfloat16 caches to 2048
# positions, 16 experts of 2048, the 262,272-row table — at TWO of the
# cell's 20 layers, which is what a test's minute allows; a layer more
# adds 0.42 GB of weights and 0.2 GB of cache and nothing else (the
# 20-layer compile's reading is in PERF.md section 4).
@pytest.mark.parametrize("mode,bucket", [("decode", 2048),
                                         ("prefill", 256)])
def test_a_cca_moe_program_compiles_at_the_published_widths(
        topo, one_chip, monkeypatch, mode, bucket):
    """The appends, the length-bounded attention reads and the grouped
    products as custom calls (the flash kernel in a 256-token prefill),
    every cache and window aliased to its output, and no temporary as
    large as a slice of a cache: composed over the cache with the key
    heads REPEATED, a decode step's attention planned 0.8 GB of them;
    composed over a slice to the bucket (until PR 54) it read 0.2 GB a
    layer whatever the slots held."""
    import json
    import os
    import re

    import paddle_tpu as pt
    from paddle_tpu.models import cca_moe
    from paddle_tpu.ops import cache_ops

    real = cache_ops.device_lane_axis
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        cache_ops, "device_lane_axis",
        lambda shape, dtype: real(shape, dtype, topo.devices[0]))
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "chipbench", "configs",
                           "zaya1-8b.json")) as f:
        cfg = json.load(f)
    layers, slots, max_seq = 2, 96, 2048
    arch = dict({k: cfg[k] for k in cca_moe.ARCH_KEYS},
                layer_types=["hybrid"] * layers)
    lm = cca_moe._build_program(
        mode, bucket, arch, cfg["vocab_size"], max_seq, slots, 0,
        dict(cca_moe.SERVED_DTYPES), 0.02)
    block = lm.main.desc.block(0)

    class EveryVarThere:         # shapes come from the program, not
        def has(self, name):     # from gigabytes of arrays in a scope
            return True

    step = pt.Executor()._compile(lm.main.desc, block, None,
                                  [lm.fetch_name], EveryVarThere())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def state(names):
        return {n: sds(block.find_var_recursive(n).shape,
                       jnp.dtype(block.find_var_recursive(n).dtype))
                for n in names}

    feed = {"token_ids": sds((slots, 1, 1), jnp.int32),
            "positions": sds((slots,), jnp.int32),
            "lengths": sds((slots,), jnp.int32)} if mode == "decode" else \
        {"token_ids": sds((1, bucket, 1), jnp.int32),
         "lengths": sds((1,), jnp.int32), "slot": sds((1,), jnp.int32)}
    compiled = step.jitted.lower(
        feed, state(step.ro_names), state(step.rw_names),
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\w[\w\-]*?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    # a layer: K, V and three windows; the grouped products are three
    # calls and their metadata
    assert len(step.rw_names) == 5 * layers
    assert calls.count("ragged-dot-none") == 3 * layers
    if mode == "decode":
        assert calls.count("kv_cache_append") == 2 * layers
        assert calls.count("decode_attention_row_major") == layers
        assert len(calls) == (2 + 1 + 4) * layers
    else:
        assert calls.count("flash_fwd") == layers
    memory = compiled.memory_analysis()
    cache = slots * 2 * max_seq * 128 * 2              # one K or V
    weights = 2 * (2 * 207_566_355 - 1 + 262272 * 2048) - 2 * 659_985 * 2
    assert memory.alias_size_in_bytes >= 2 * layers * cache
    assert weights < memory.argument_size_in_bytes \
        < weights + 2 * layers * cache + (64 << 20)
    assert memory.temp_size_in_bytes < 32 << 20


# The delta-rule family's decode step (models/delta_hybrid.py,
# Olmo-Hybrid-7B's widths: 40 slots, 30 heads of a 96 x 192 float32
# matrix state; 30 key heads of 128, bfloat16 caches to 2048 positions).
def test_delta_state_update_compiles_in_place(one_chip):
    """One custom call, the 88-MB state aliased to its output, nothing
    state-sized copied or planned as a temporary."""
    import re

    from paddle_tpu.ops.pallas.delta_state_update import delta_state_update

    slots, heads, d_k, d_v = 40, 30, 96, 192

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda s, q, k, v, a, b: delta_state_update(s, q, k, v, a, b,
                                                    interpret=False),
        donate_argnums=0).lower(
        sds(slots, d_k, heads * d_v), sds(slots, heads, d_k),
        sds(slots, heads, d_k), sds(slots, heads * d_v),
        sds(slots, heads), sds(slots, heads)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"%delta_state_update\S* = ", text)  # named for the trace
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= slots * d_k * heads * d_v * 4
    assert memory.temp_size_in_bytes < 8 << 20
    assert not re.findall(r"= f32\[40,96,5760\]\S* copy\(", text)


@pytest.mark.parametrize("mode,bucket", [("decode", 2048),
                                         ("prefill", 1024)])
def test_a_delta_hybrid_program_compiles_at_the_published_widths(
        topo, one_chip, monkeypatch, mode, bucket):
    """ONE period of the cell's four (three linear layers and a full
    one, which is what a test's minute allows): the state updates and
    the appends as custom calls, every state aliased to its output, and
    what the decode step's attention is handed — 30 key heads of 128
    are more than ``decode_attention``'s row-major body holds in flight
    (19.7 MB against 8), so the site is COMPOSED over a slice to the
    bucket (PERF.md section 7)."""
    import json
    import os
    import re

    import paddle_tpu as pt
    from paddle_tpu.models import delta_hybrid
    from paddle_tpu.ops import cache_ops
    from paddle_tpu.ops.pallas import decode_attention

    real = cache_ops.device_lane_axis
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        cache_ops, "device_lane_axis",
        lambda shape, dtype: real(shape, dtype, topo.devices[0]))
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "chipbench", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    layers, slots, max_seq = 4, 40, 2048
    cache = (slots, 30, max_seq, 128)
    assert real(cache, jnp.bfloat16, topo.devices[0]) == 3
    assert not decode_attention.fits(cache, jnp.bfloat16, 3, bucket)
    arch = dict({k: cfg[k] for k in delta_hybrid.ARCH_KEYS},
                layer_types=cfg["layer_types"][:layers])
    before = _site_counts("paddle_tpu_sdpa_sites_total")
    lm = delta_hybrid._build_program(
        mode, bucket, arch, cfg["vocab_size"], max_seq, slots, 0,
        dict(delta_hybrid.SERVED_DTYPES), 4.0)
    block = lm.main.desc.block(0)

    class EveryVarThere:         # shapes come from the program, not
        def has(self, name):     # from gigabytes of arrays in a scope
            return True

    step = pt.Executor()._compile(lm.main.desc, block, None,
                                  [lm.fetch_name], EveryVarThere())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def state(names):
        return {n: sds(block.find_var_recursive(n).shape,
                       jnp.dtype(block.find_var_recursive(n).dtype))
                for n in names}

    feed = {"token_ids": sds((slots, 1, 1), jnp.int32),
            "positions": sds((slots,), jnp.int32),
            "lengths": sds((slots,), jnp.int32)} if mode == "decode" else \
        {"token_ids": sds((1, bucket, 1), jnp.int32),
         "lengths": sds((1,), jnp.int32), "slot": sds((1,), jnp.int32)}
    compiled = step.jitted.lower(
        feed, state(step.ro_names), state(step.rw_names),
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(\w[\w\-]*?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    # a period: K and V, nine windows and three matrix states
    assert len(step.rw_names) == 2 + 9 + 3
    sites = _site_counts("paddle_tpu_sdpa_sites_total") - before
    if mode == "decode":
        assert sorted(calls) == ["delta_state_update"] * 3 \
            + ["kv_cache_append"] * 2
        assert sites == {"composed": 1}
    else:
        assert calls == ["flash_fwd"] and sites == {"flash": 1}
        # the scan over a prompt's chunks, one a linear layer
        assert len(re.findall(r"= \S.* while\(", text)) == 3
    memory = compiled.memory_analysis()
    held = 2 * slots * 30 * max_seq * 128 * 2 \
        + 3 * slots * 96 * 5760 * 4               # K, V, three states
    assert memory.alias_size_in_bytes >= held
    weights = 2 * (832_520_436 + 2 * 385_351_680)
    assert weights < memory.argument_size_in_bytes \
        < weights + held + (64 << 20)
    assert memory.temp_size_in_bytes < 256 << 20
