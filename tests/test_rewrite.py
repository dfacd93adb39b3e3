"""ProgramDesc rewrite layer (analysis/rewrite.py): pass-level units,
executor integration, fusion outlining onto the Pallas kernels, the
broken-rewrite fallback, and the 9-network loss-identity gate.

Tolerance policy (documented per pattern, not blanket):
- dce / cse / const_fold / grad_prune / kernel annotation: BIT-identical
  losses required — these passes never change the traced math.
- attention outlining, naive path: bit-identical (the sdpa op's einsum
  contracts the same dims the composed matmul chain does).
- attention outlining with the flash kernel engaged (force, interpret):
  allclose atol=2e-6 per step — the online-softmax recurrence changes
  f32 accumulation order.
- SE-block outlining: allclose atol=1e-6 — the mega-op pools via an
  f32 sum/size instead of pool2d's reduce_window (same math, fused
  epilogue).
"""
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.analysis import rewrite
from paddle_tpu.core.lod import LoDTensor


def _fetch_scalar(exe, program, feed, fetch):
    (v,) = exe.run(program, feed=feed, fetch_list=[fetch])
    return float(np.ravel(np.asarray(v))[0])


def _train_losses(main, startup, loss, feed, steps=3):
    scope, exe = pt.Scope(), pt.Executor()
    with pt.scope_guard(scope):
        exe.run(startup)
        return [_fetch_scalar(exe, main, feed, loss)
                for _ in range(steps)]


# ---------------------------------------------------------------------------
# individual passes
# ---------------------------------------------------------------------------
def test_dce_removes_dead_ops_and_keeps_results(monkeypatch):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        live = layers.fc(x, size=3)
        layers.scale(x, 5.0)               # dead: contributes to nothing
        layers.elementwise_mul(x, x)       # dead
        out = layers.mean(live)
    res = rewrite.rewrite_program(main, feed_names=["x"],
                                  fetch_names=[out.name])
    assert res.changed
    assert res.count("dce", "remove_op") == 2
    types = [op.type for op in res.program.global_block.ops]
    assert "scale" not in types and "elementwise_mul" not in types
    feed = {"x": np.random.RandomState(0).rand(2, 4).astype(np.float32)}
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, out, feed, 1)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    on = _train_losses(main, startup, out, feed, 1)
    assert off == on


def test_dce_keeps_effects_and_attr_referenced_ops():
    """Persistable writers, sub-block owners, and ops referenced only
    through control-flow attrs (While cond/carried names) survive."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        i = layers.fill_constant([1], "int32", 0)
        n = layers.fill_constant([1], "int32", 3)
        s = layers.fc(x, size=4)
        w = layers.While(layers.less_than(i, n), max_steps=8)
        with w.block():
            layers.assign(layers.elementwise_add(s, s), s)
            layers.assign(layers.increment(i, in_place=False), i)
        out = layers.mean(s)
    res = rewrite.rewrite_program(main, feed_names=["x"],
                                  fetch_names=[out.name])
    types = [op.type for op in res.program.global_block.ops]
    # the loop machinery (fill_constants feeding cond/carry via attrs,
    # less_than, while) must all survive
    assert types.count("fill_constant") == 2
    assert "less_than" in types and "while" in types


def test_cse_merges_duplicates_bit_identical(monkeypatch):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        a = layers.scale(x, 3.0)
        b = layers.scale(x, 3.0)           # identical computation
        out = layers.mean(layers.elementwise_add(a, b))
    res = rewrite.rewrite_program(main, feed_names=["x"],
                                  fetch_names=[out.name])
    assert res.count("cse", "merge_op") == 1
    feed = {"x": np.random.RandomState(1).rand(2, 4).astype(np.float32)}
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, out, feed, 1)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    on = _train_losses(main, startup, out, feed, 1)
    assert off == on


def test_cse_respects_optimizer_update_ordering(monkeypatch):
    """Regression (review find): a persistable param its optimizer
    writes exactly once is still single-writer — two identical reads on
    OPPOSITE sides of the update must not merge, or the post-update
    read aliases to the stale pre-update value."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        w = layers.create_parameter([4, 3], "float32")
        y1 = layers.mul(x, w)
        loss = layers.mean(y1)
        optimizer.SGDOptimizer(learning_rate=0.5).minimize(loss)
        # identical projection built AFTER the sgd update: it reads the
        # post-step weights
        y2 = layers.mul(x, w)
        post = layers.mean(y2)
    res = rewrite.rewrite_program(
        main, feed_names=["x"], fetch_names=[loss.name, post.name])
    assert res.count("cse", "merge_op") == 0
    feed = {"x": np.random.RandomState(5).rand(2, 4).astype(np.float32)}

    def run(env_val):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", env_val)
        scope, exe = pt.Scope(), pt.Executor()
        with pt.scope_guard(scope):
            exe.run(startup)
            vals = exe.run(main, feed=feed, fetch_list=[loss, post])
            return [float(np.ravel(v)[0]) for v in vals]

    assert run("0") == run("1")


def test_outline_failure_does_not_block_later_sites():
    """Regression (review find): a site refused by the safety checks
    (here: attention probs additionally fetched — an external consumer
    of a chain intermediate) must not stop later sites from
    outlining."""
    B, H, S, D = 2, 2, 8, 4
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [H, S, D])
        k = layers.data("k", [H, S, D])
        v = layers.data("v", [H, S, D])

        def attention(qv, kv, vv):
            scores = layers.matmul(qv, kv, transpose_y=True,
                                   alpha=float(1.0 / np.sqrt(D)))
            probs = layers.softmax(scores)
            return probs, layers.matmul(probs, vv)

        probs1, ctx1 = attention(q, k, v)       # probs1 gets fetched
        _probs2, ctx2 = attention(ctx1, k, v)   # clean site
        out = layers.mean(layers.elementwise_add(ctx1, ctx2))
    res = rewrite.rewrite_program(
        main, feed_names=["q", "k", "v"],
        fetch_names=[out.name, probs1.name])
    assert res.count("fuse_attention", "outline") == 1
    types = [op.type for op in res.program.global_block.ops]
    # site 1 stays composed (its probs are fetched), site 2 outlined
    assert types.count("scaled_dot_product_attention") == 1
    assert types.count("softmax") == 1


def test_cse_respects_inplace_self_write(monkeypatch):
    """Regression (review find): when the shared input's single write
    IS one of the two candidates (increment(x, in_place=True)), the
    two reads straddle the write — merging would alias the later read
    to the pre-write value (off: 3.0, on would read 2.0)."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [1])
        layers.increment(x, in_place=True)       # writes x itself
        m = layers.increment(x, in_place=False)  # reads POST-write x
        out = layers.scale(m, 1.0)
    res = rewrite.rewrite_program(main, feed_names=["x"],
                                  fetch_names=[out.name])
    assert res.count("cse", "merge_op") == 0
    feed = {"x": np.ones((1, 1), np.float32)}

    def run(env_val):
        monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", env_val)
        scope, exe = pt.Scope(), pt.Executor()
        with pt.scope_guard(scope):
            exe.run(startup)
            (v,) = exe.run(main, feed=feed, fetch_list=[out])
            return float(np.ravel(v)[0])

    assert run("0") == run("1") == 3.0


def test_cse_never_merges_random_ops():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        d1 = layers.dropout(x, 0.5)
        d2 = layers.dropout(x, 0.5)
        out = layers.mean(layers.elementwise_add(d1, d2))
    res = rewrite.rewrite_program(main, feed_names=["x"],
                                  fetch_names=[out.name])
    assert res.count("cse", "merge_op") == 0


def test_const_fold_bakes_literal_chains(monkeypatch):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        c = layers.fill_constant([4], "float32", 3.0)
        c2 = layers.scale(c, 2.0)                     # = 6.0
        c3 = layers.elementwise_add(c2, c)            # = 9.0
        out = layers.mean(layers.elementwise_add(x, c3))
    res = rewrite.rewrite_program(main, feed_names=["x"],
                                  fetch_names=[out.name])
    assert res.count("const_fold", "fold_op") == 2
    folded = [op for op in res.program.global_block.ops
              if op.type == "assign_value"
              and op.attrs.get("__folded_from__")]
    assert folded, "folded literal op missing"
    assert np.allclose(folded[-1].attrs["values"], 9.0)
    feed = {"x": np.random.RandomState(2).rand(2, 4).astype(np.float32)}
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, out, feed, 1)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    on = _train_losses(main, startup, out, feed, 1)
    assert off == on


# ---------------------------------------------------------------------------
# attention outlining
# ---------------------------------------------------------------------------
_ATT = dict(B=2, H=2, S=8, D=4)


def _build_composed_attention(with_mask=True):
    B, H, S, D = _ATT["B"], _ATT["H"], _ATT["S"], _ATT["D"]
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [H, S, D])
        k = layers.data("k", [H, S, D])
        v = layers.data("v", [H, S, D])
        label = layers.data("label", [H, S, D])
        qp = layers.fc(q, size=D, num_flatten_dims=3, bias_attr=False,
                       name="wq")
        kp = layers.fc(k, size=D, num_flatten_dims=3, bias_attr=False,
                       name="wk")
        vp = layers.fc(v, size=D, num_flatten_dims=3, bias_attr=False,
                       name="wv")
        scores = layers.matmul(qp, kp, transpose_y=True,
                               alpha=float(1.0 / np.sqrt(D)))
        if with_mask:
            mask = layers.assign(
                np.triu(np.full((S, S), -1e9, np.float32), k=1))
            scores = layers.elementwise_add(scores, mask)
        probs = layers.softmax(scores)
        ctxv = layers.matmul(probs, vp)
        loss = layers.mean(layers.square(ctxv - label))
        optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _attention_feed():
    B, H, S, D = _ATT["B"], _ATT["H"], _ATT["S"], _ATT["D"]
    rng = np.random.RandomState(0)
    return {n: rng.rand(B, H, S, D).astype(np.float32)
            for n in ("q", "k", "v", "label")}


@pytest.mark.parametrize("with_mask", [False, True])
def test_attention_outlining_merges_forward_and_backward(with_mask):
    main, startup, loss = _build_composed_attention(with_mask)
    feeds = ["q", "k", "v", "label"]
    res = rewrite.rewrite_program(main, feed_names=feeds,
                                  fetch_names=[loss.name])
    assert res.count("fuse_attention", "outline") == 1
    root = res.program.global_block
    sdpa = [op for op in root.ops
            if op.type == "scaled_dot_product_attention"]
    assert len(sdpa) == 1
    # the chain's softmax/matmuls are gone from the forward section
    assert not any(op.type == "softmax" for op in root.ops)
    # exactly one merged __vjp__ embeds the mega-op; the chain's
    # per-op grad ops are gone
    merged = [op for op in root.ops if op.type == "__vjp__"
              and op.attrs["fwd_op"]["type"]
              == "scaled_dot_product_attention"]
    assert len(merged) == 1
    assert not any(op.type == "__vjp__"
                   and op.attrs["fwd_op"]["type"] in ("softmax", "matmul")
                   for op in root.ops)
    if with_mask:
        assert sdpa[0].input("Mask")
        # the mask is a constant bias: the merged grad op must not
        # request its gradient (flash treats bias as constant)
        fwd_in = merged[0].input("FwdIn")
        need = merged[0].attrs["in_need_grad"]
        mask_name = sdpa[0].input("Mask")[0]
        assert not any(n for nm, n in zip(fwd_in, need)
                       if nm == mask_name)
    # the user's exact softmax scale rides on the op
    assert sdpa[0].attrs["scale"] == pytest.approx(
        1.0 / np.sqrt(_ATT["D"]))


def test_attention_outline_losses_bit_identical_naive(monkeypatch):
    feed = _attention_feed()
    main, startup, loss = _build_composed_attention(True)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, loss, feed)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    on = _train_losses(main, startup, loss, feed)
    # naive sdpa path: identical contraction dims -> bit-identical
    assert off == on


def test_attention_outline_engages_flash_kernel(monkeypatch):
    """Acceptance: outlining engages the Pallas flash kernel on a
    user-built attention program — forward AND backward (the merged
    __vjp__ replays the annotated mega-op) — with no TPU, via force
    dispatch (interpret mode)."""
    import paddle_tpu.ops.pallas as pallas_pkg

    feed = _attention_feed()
    main, startup, loss = _build_composed_attention(True)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, loss, feed)

    calls = []
    orig = pallas_pkg.flash_attention

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    import sys
    # the package re-exports the function under the module's name
    fa_mod = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    bwd_calls = []
    orig_bwd = fa_mod._bwd

    def counting_bwd(*a, **kw):
        bwd_calls.append(1)
        return orig_bwd(*a, **kw)

    monkeypatch.setattr(pallas_pkg, "flash_attention", counting)
    monkeypatch.setattr(fa_mod, "_bwd", counting_bwd)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", "force")
    on = _train_losses(main, startup, loss, feed)
    # traced ONCE, in the forward sdpa op: the merged __vjp__ applies
    # that trace's pullback (the flash custom-vjp backward) and does
    # not replay the forward kernel (tests/test_vjp_reuse.py)
    assert len(calls) == 1, "flash forward traced %d times" % len(calls)
    assert len(bwd_calls) == 1, "flash backward did not engage"
    # documented tolerance: online-softmax accumulation order
    assert np.allclose(off, on, atol=2e-6), (off, on)


# ---------------------------------------------------------------------------
# SE-block outlining
# ---------------------------------------------------------------------------
def _build_se():
    from paddle_tpu.models.resnet import squeeze_excitation
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8, 4, 4])
        lbl = layers.data("lbl", [8, 4, 4])
        gated = squeeze_excitation(x, 8, reduction_ratio=4)
        loss = layers.mean(layers.square(gated - lbl))
        optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_se_block_outlining(monkeypatch):
    main, startup, loss = _build_se()
    res = rewrite.rewrite_program(main, feed_names=["x", "lbl"],
                                  fetch_names=[loss.name])
    assert res.count("fuse_se", "outline") == 1
    root = res.program.global_block
    se = [op for op in root.ops if op.type == "se_block"]
    assert len(se) == 1
    assert sorted(se[0].inputs) == ["B1", "B2", "W1", "W2", "X"]
    assert not any(op.type in ("pool2d", "sigmoid") for op in root.ops)
    rng = np.random.RandomState(3)
    feed = {"x": rng.rand(2, 8, 4, 4).astype(np.float32),
            "lbl": rng.rand(2, 8, 4, 4).astype(np.float32)}
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, loss, feed)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    on = _train_losses(main, startup, loss, feed)
    # documented tolerance: the mega-op pools via f32 sum/size instead
    # of reduce_window (same math, different reduction lowering)
    assert np.allclose(off, on, atol=1e-6), (off, on)


# ---------------------------------------------------------------------------
# kernel dispatch on the stacked-LSTM network
# ---------------------------------------------------------------------------
def _build_lstm_lm():
    from paddle_tpu.models import lstm_lm
    return lstm_lm.build_train(vocab_size=50, emb_dim=8, hid_dim=8,
                               num_layers=2)


def _lstm_feed():
    rng = np.random.RandomState(1)
    data = rng.randint(0, 50, size=(10, 1)).astype(np.int64)
    lod = [[0, 4, 7, 10]]
    return {"words": LoDTensor(data, lod),
            "targets": LoDTensor(data, lod)}


def test_lstm_dispatch_annotates_and_engages(monkeypatch):
    """Acceptance: the rewrite engages fused_lstm on the stacked-LSTM
    network. The kernel call itself is proven with a sentinel spy (the
    Pallas kernels only compile on TPU; interpret mode covers them in
    test_fused_lstm) and the dispatch decision is program-visible as
    the __pallas__ attr."""
    import paddle_tpu.ops.pallas.fused_lstm as fl

    main, startup, fetches = _build_lstm_lm()
    loss = fetches["loss"]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_LSTM", "force")
    res = rewrite.rewrite_program(
        main, feed_names=["words", "targets"], fetch_names=[loss.name])
    ann = [op.attrs.get("__pallas__")
           for op in res.program.global_block.ops if op.type == "lstm"]
    assert ann == ["force", "force"]
    assert res.count("kernel_dispatch", "dispatch") >= 2

    class _Sentinel(Exception):
        pass

    def spy(*a, **kw):
        raise _Sentinel("fused_lstm engaged")

    monkeypatch.setattr(fl, "fused_lstm", spy)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    scope, exe = pt.Scope(), pt.Executor()
    with pt.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(Exception) as ei:
            exe.run(main, feed=_lstm_feed(), fetch_list=[loss])
    assert "fused_lstm engaged" in str(ei.value)


def test_lstm_losses_bit_identical_on_scan_path(monkeypatch):
    """Off-TPU the '1' annotation resolves to the scan path in both
    arms — losses must be bit-identical."""
    main, startup, fetches = _build_lstm_lm()
    loss = fetches["loss"]
    feed = _lstm_feed()
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, loss, feed)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    on = _train_losses(main, startup, loss, feed)
    assert off == on


# ---------------------------------------------------------------------------
# safety net: broken rewrites fall back
# ---------------------------------------------------------------------------
class _BreakingPass(rewrite.RewritePass):
    """Deliberately corrupts the program: dangling input."""

    name = "deliberately_broken"

    def apply(self, program, ctx):
        root = program.blocks[ctx.block_idx]
        root.ops[0] = type(root.ops[0])(
            "elementwise_add",
            {"X": ["__no_such_var__"], "Y": ["__no_such_var__"]},
            {"Out": root.ops[0].output_names() or ["__broken_out__"]})
        return [{"action": "corrupt"}]


class _RaisingPass(rewrite.RewritePass):
    name = "raising"

    def apply(self, program, ctx):
        raise RuntimeError("pass blew up")


def test_broken_rewrite_falls_back_to_unrewritten(monkeypatch):
    """Acceptance: a deliberately-broken rewrite (test-injected) is
    rejected by the post-rewrite fast_passes() verification and the
    executor compiles the unrewritten program instead of garbage."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        out = layers.mean(layers.fc(x, size=3))
    feed = {"x": np.random.RandomState(4).rand(2, 4).astype(np.float32)}
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    expected = _train_losses(main, startup, out, feed, 1)

    monkeypatch.setattr(
        rewrite, "default_rewrite_passes",
        lambda: [_BreakingPass(), _RaisingPass()])
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    got = _train_losses(main, startup, out, feed, 1)
    assert got == expected

    # both passes were counted as aborted, nothing was adopted
    res = rewrite.rewrite_program(main, feed_names=["x"],
                                  fetch_names=[out.name])
    assert not res.changed
    assert res.aborted == ["deliberately_broken", "raising"]
    # ... and the abort is visible in the metrics ledger
    from paddle_tpu.observability import default_registry
    fam = default_registry().get("paddle_tpu_rewrite_ops_total")
    keys = {key for key, _ in fam.samples()}
    assert ("deliberately_broken", "aborted") in keys


def test_rewrite_never_mutates_the_original_program():
    main, startup, loss = _build_composed_attention(True)
    before = main.desc.to_json()
    res = rewrite.rewrite_program(
        main, feed_names=["q", "k", "v", "label"],
        fetch_names=[loss.name])
    assert res.changed
    assert main.desc.to_json() == before


def test_optimize_kill_switch(monkeypatch):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        layers.scale(x, 2.0)   # dead
        out = layers.mean(layers.fc(x, size=2))
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    scope, exe = pt.Scope(), pt.Executor()
    feed = {"x": np.zeros((1, 4), np.float32)}
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[out])
    compiled = next(iter(exe._cache.values()))
    assert compiled.rewrite is None


def test_rewrite_metrics_published():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        layers.scale(x, 2.0)   # dead -> guaranteed dce action
        out = layers.mean(layers.fc(x, size=2))
    rewrite.rewrite_program(main, feed_names=["x"],
                            fetch_names=[out.name])
    from paddle_tpu.observability import default_registry
    reg = default_registry()
    assert reg.get("paddle_tpu_rewrite_seconds") is not None
    keys = {key for key, _ in
            reg.get("paddle_tpu_rewrite_ops_total").samples()}
    assert ("dce", "remove_op") in keys


# ---------------------------------------------------------------------------
# cost-model rules for the mega-ops
# ---------------------------------------------------------------------------
def test_cost_model_covers_outlined_mega_ops():
    from paddle_tpu.analysis import cost_model

    main, startup, loss = _build_composed_attention(True)
    res = rewrite.rewrite_program(
        main, feed_names=["q", "k", "v", "label"],
        fetch_names=[loss.name])
    B, H, S, D = _ATT["B"], _ATT["H"], _ATT["S"], _ATT["D"]
    cost = cost_model.program_cost(res.program, batch=B)
    sdpa = [c for c in cost.ops
            if c.op_type == "scaled_dot_product_attention"]
    assert len(sdpa) == 1
    assert sdpa[0].exact
    assert sdpa[0].flops == 4 * B * H * S * S * D + 5 * B * H * S * S

    main, startup, loss = _build_se()
    res = rewrite.rewrite_program(main, feed_names=["x", "lbl"],
                                  fetch_names=[loss.name])
    cost = cost_model.program_cost(res.program, batch=2)
    se = [c for c in cost.ops if c.op_type == "se_block"]
    assert len(se) == 1 and se[0].exact
    # 2 flops/elem activation sweeps + two bottleneck FCs (c=8, r=2)
    assert se[0].flops == 2 * (2 * 8 * 4 * 4) + 4 * 2 * 8 * 2

    main, startup, fetches = _build_lstm_lm()
    cost = cost_model.program_cost(main, batch=4)
    lstm = [c for c in cost.ops if c.op_type == "lstm"]
    assert lstm and all(c.exact for c in lstm)


# ---------------------------------------------------------------------------
# the 9-network loss-identity gate
# ---------------------------------------------------------------------------
def _network_feed(name):
    rng = np.random.RandomState(7)
    if name == "fc_regression":
        return {"x": rng.rand(2, 13).astype(np.float32),
                "y": rng.rand(2, 1).astype(np.float32)}
    if name == "mnist_mlp":
        return {"img": rng.rand(2, 784).astype(np.float32),
                "label": rng.randint(0, 10, (2, 1)).astype(np.int64)}
    if name == "mnist_conv":
        return {"img": rng.rand(2, 1, 28, 28).astype(np.float32),
                "label": rng.randint(0, 10, (2, 1)).astype(np.int64)}
    if name == "seq_pool":
        return {"seq": LoDTensor(rng.rand(5, 16).astype(np.float32),
                                 [[0, 3, 5]]),
                "y": rng.rand(2, 1).astype(np.float32)}
    if name == "embedding_lm":
        return {"words": LoDTensor(
                    rng.randint(0, 100, (6, 1)).astype(np.int64),
                    [[0, 2, 6]]),
                "label": rng.randint(0, 100, (2, 1)).astype(np.int64)}
    if name == "while_loop":
        return {"x": rng.rand(2, 4).astype(np.float32)}
    if name == "static_rnn":
        return {"x": rng.rand(5, 4, 8).astype(np.float32)}
    if name == "dynamic_rnn":
        return {"sent": LoDTensor(rng.rand(5, 8).astype(np.float32),
                                  [[0, 2, 5]])}
    if name == "ifelse":
        return {"x": rng.rand(2, 4).astype(np.float32)}
    raise KeyError(name)


def _lint_networks():
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from lint_ir import NETWORKS
    return NETWORKS


@pytest.mark.parametrize("name", [
    "fc_regression", "mnist_mlp", "mnist_conv", "seq_pool",
    "embedding_lm", "while_loop", "static_rnn", "dynamic_rnn",
    "ifelse"])
def test_loss_identity_gate(name, monkeypatch):
    """Acceptance: optimization-on training is loss-identical to
    optimization-off across the 9 lint networks, 3 steps each. None of
    these graphs contains an outlinable pattern, so EXACT equality is
    required (the documented tolerances apply only to outlined
    kernels — see the module docstring)."""
    networks = _lint_networks()
    main, startup, _feeds, fetches = networks[name]()
    feed = _network_feed(name)
    loss = fetches[0]
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "0")
    off = _train_losses(main, startup, loss, feed)
    monkeypatch.setenv("PADDLE_TPU_OPTIMIZE", "1")
    on = _train_losses(main, startup, loss, feed)
    assert off == on, f"{name}: optimization changed training losses"
