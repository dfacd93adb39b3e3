"""The generic grad op reuses its forward op's pullback (ISSUE 27).

`trace_block` runs a forward op whose `__vjp__` comes later in the same
block ONCE, under jax.vjp, and the grad op applies that pullback instead
of replaying the forward rule — so a Pallas kernel in the rule (which
XLA's CSE does not merge) runs once a site. Three invariants keep every
other program as it was: I1 a block without a `__vjp__` is traced as
before, I2 a pullback never outlives the `trace_block` call that made
it, I3 the pullback is applied only where every FwdIn value IS the
value the forward op consumed (else the rule is replayed).
"""
import collections
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.lod import LoDTensor
from paddle_tpu.layers import control_flow as cf
from paddle_tpu.observability import default_registry
from paddle_tpu.ops import core_ops

SDPA = "scaled_dot_product_attention"


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _site_counts():
    """{(served, forward op type): grad sites traced so far}."""
    fam = default_registry().get("paddle_tpu_grad_sites_total")
    if fam is None:
        return collections.Counter()
    return collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


def _served(delta, served, op=None):
    return sum(v for (s, o), v in delta.items()
               if s == served and (op is None or o == op))


class _counting:
    """with _counting() as d: ... -> d.delta is what the block traced."""

    def __enter__(self):
        self.before = _site_counts()
        return self

    def __exit__(self, *exc):
        after = _site_counts()
        self.delta = collections.Counter(
            {k: after[k] - self.before.get(k, 0) for k in after
             if after[k] != self.before.get(k, 0)})


def _replay_only(monkeypatch):
    """No look-ahead: every `_vjp` is called with no pullback."""
    monkeypatch.setattr(executor_mod, "_vjp_sites", lambda ops: None)


def _step_jaxpr(exe, program, scope=None):
    """The jaxpr of the cached step of `program`, traced again with the
    state and the feed of its last run."""
    scope = pt.global_scope() if scope is None else scope
    uid = program.desc.uid
    entry = next(v for k, v in exe._cache.items() if k[0] == uid)
    traced = entry.jitted.trace(
        [exe._last_feed_vals[k] for k in entry.feed_names],
        scope.values_of(entry.ro_names), scope.values_of(entry.rw_names),
        jnp.zeros((), jnp.int32))
    return traced.jaxpr


def _kernel_calls(jaxpr):
    """{pallas_call name: count} over a jaxpr and every jaxpr inside."""
    calls = collections.Counter()

    def walk(j):
        j = getattr(j, "jaxpr", j)
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                        walk(sub)

    walk(jaxpr)
    return calls


def _sdpa_op(q, k, v, use_flash=True):
    from paddle_tpu.layer_helper import LayerHelper
    helper = LayerHelper("sdpa")
    out = helper.create_tmp_variable("float32")
    helper.append_op(type=SDPA, inputs={"Q": q, "K": k, "V": v},
                     outputs={"Out": out}, attrs={"use_flash": use_flash})
    return out


_ATT = dict(B=2, H=2, S=32, D=8)


def _att_feed(seed=0):
    rng = np.random.RandomState(seed)
    shape = (_ATT["B"], _ATT["H"], _ATT["S"], _ATT["D"])
    return {n: rng.randn(*shape).astype(np.float32)
            for n in ("q", "k", "v", "label")}


def _build_sdpa_hand():
    """Two hand-placed SDPA ops (flash, interpret mode) in a train step."""
    H, S, D = _ATT["H"], _ATT["S"], _ATT["D"]
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [H, S, D])
        k = layers.data("k", [H, S, D])
        v = layers.data("v", [H, S, D])
        label = layers.data("label", [H, S, D])
        proj = [layers.fc(t, size=D, num_flatten_dims=3, bias_attr=False)
                for t in (q, k, v)]
        a1 = _sdpa_op(*proj)
        a2 = _sdpa_op(a1, proj[1], proj[2])
        loss = layers.mean(layers.square(a2 - label))
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss, _att_feed(), {"flash": 2}


def _ragged_ids(seed=1, vocab=50):
    rng = np.random.RandomState(seed)
    data = rng.randint(0, vocab, size=(10, 1)).astype(np.int64)
    return LoDTensor(data, [[0, 4, 7, 10]])


def _build_lstm():
    from paddle_tpu.models import lstm_lm
    main, startup, fetches = lstm_lm.build_train(
        vocab_size=50, emb_dim=8, hid_dim=8, num_layers=2)
    ids = _ragged_ids()
    return (main, startup, fetches["loss"],
            {"words": ids, "targets": ids}, {"fused_lstm": 2})


def _build_gru():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        words = layers.data("words", [1], dtype="int64", lod_level=1)
        tgt = layers.data("tgt", [8], dtype="float32")
        emb = layers.embedding(words, size=[50, 8])
        h = layers.dynamic_gru(layers.fc(emb, size=24), size=8)
        last = layers.sequence_pool(h, pool_type="last")
        loss = layers.mean(layers.square(last - tgt))
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    feed = {"words": _ragged_ids(),
            "tgt": np.random.RandomState(2).randn(3, 8).astype(np.float32)}
    return main, startup, loss, feed, {"fused_gru": 1}


def _run_once(builder, monkeypatch, exe_factory=pt.Executor):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", "force")
    monkeypatch.setenv("PADDLE_TPU_PALLAS_LSTM", "force")
    monkeypatch.setenv("PADDLE_TPU_PALLAS_GRU", "force")
    main, startup, loss, feed, sites = builder()
    pt.Executor().run(startup)
    exe = exe_factory()
    with _counting() as c:
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(np.asarray(lv)).all()
    return main, exe, sites, c.delta


def _mesh_executor():
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.executor import ParallelExecutor, ShardingSpec
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    return ParallelExecutor(mesh=mesh,
                            sharding=ShardingSpec(feed_axis="data"))


# ---------------------------------------------------------------------------
# (1) kernel calls a site
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("builder,exe_factory", [
    (_build_sdpa_hand, pt.Executor),
    (_build_sdpa_hand, _mesh_executor),
    (_build_lstm, pt.Executor),
    (_build_gru, pt.Executor),
], ids=["sdpa-hand-placed", "sdpa-under-shard_map", "fused_lstm",
        "fused_gru"])
def test_one_forward_kernel_call_a_site(builder, exe_factory, monkeypatch):
    main, exe, sites, delta = _run_once(builder, monkeypatch, exe_factory)
    calls = _kernel_calls(_step_jaxpr(exe, main))
    want = {}
    for kind, n in sites.items():
        if kind == "flash":
            want.update(flash_fwd=n, flash_bwd_dkv_dq=n)
        else:
            want.update({kind + "_fwd": n, kind + "_bwd": n})
    assert dict(calls) == want
    op = {"flash": SDPA, "fused_lstm": "lstm", "fused_gru": "gru"}[
        next(iter(sites))]
    assert _served(delta, "reused", op) == sum(sites.values())
    assert _served(delta, "replayed", op) == 0


def test_replay_runs_the_forward_kernel_twice_a_site(monkeypatch):
    """What the look-ahead removes: without it (the parent's tracing)
    every flash site holds two forward kernels."""
    _replay_only(monkeypatch)
    main, exe, sites, delta = _run_once(_build_sdpa_hand, monkeypatch)
    calls = _kernel_calls(_step_jaxpr(exe, main))
    assert calls["flash_fwd"] == 2 * sites["flash"]
    assert calls["flash_bwd_dkv_dq"] == sites["flash"]
    assert _served(delta, "reused") == 0
    assert _served(delta, "replayed", SDPA) == sites["flash"]


# ---------------------------------------------------------------------------
# (2) reuse and replay give the same gradients, to the bit
# ---------------------------------------------------------------------------
def _mlp_feed(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(16, 4).astype(np.float32)
    return {"x": x, "label": (x.sum(1, keepdims=True) > 2.0)
            .astype(np.float32)}


def _case_plain():
    x = layers.data("x", [4], dtype="float32")
    label = layers.data("label", [1], dtype="float32")
    pred = layers.fc(layers.fc(x, size=8, act="tanh"), size=1)
    loss = layers.mean(layers.square_error_cost(pred, label))
    pt.optimizer.MomentumOptimizer(learning_rate=0.05,
                                   momentum=0.9).minimize(loss)
    return loss, _mlp_feed()


def _case_snapshot():
    """An in-place op on the grad path: backward.py feeds its grad op a
    @PRE. snapshot of the value it overwrote."""
    x = layers.data("x", [4], dtype="float32")
    label = layers.data("label", [1], dtype="float32")
    s = layers.fc(x, size=1, act="tanh")
    layers.increment(s, value=1.0, in_place=True)
    loss = layers.mean(layers.square(s - label))
    pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return loss, _mlp_feed()


def _case_bounded_while():
    x = layers.data("x", [2], dtype="float32")
    s = layers.fc(x, size=2, act="tanh")
    counter = layers.fill_constant([1], "int64", 0)
    limit = layers.fill_constant([1], "int64", 3)
    cond = cf.less_than_v(counter, limit)
    w = cf.While(cond, max_steps=8)
    with w.block():
        layers.assign(layers.scale(s, scale=0.5), output=s)
        layers.increment(counter, value=1.0, in_place=True)
        cf.less_than_v(counter, limit, cond=cond)
    loss = layers.mean(s)
    pt.optimizer.SGDOptimizer(learning_rate=0.5).minimize(loss)
    return loss, {"x": np.ones((2, 2), np.float32)}


def _ragged_floats(seed=0):
    rng = np.random.RandomState(seed)
    # longest 5: a [batch, 4] memory must not look like [batch, time]
    seqs = [rng.randn(n, 3).astype(np.float32) for n in (5, 2, 3)]
    return LoDTensor.from_sequences(seqs)


def _case_dynamic_rnn_closure():
    """fc parameters made inside the DynamicRNN block reach the grad op
    as closure_names."""
    x = layers.data("x", [3], dtype="float32", lod_level=1)
    tgt = layers.data("tgt", [4], dtype="float32")
    drnn = cf.DynamicRNN()
    with drnn.block():
        w = drnn.step_input(x)
        prev = drnn.memory(shape=[4], value=0.0)
        nxt = layers.fc(w, size=4, act="tanh") + prev
        drnn.update_memory(prev, nxt)
        drnn.output(nxt)
    drnn()
    loss = layers.mean(layers.square(drnn.last_memory() - tgt))
    pt.optimizer.AdamOptimizer(learning_rate=0.05).minimize(loss)
    return loss, {"x": _ragged_floats(),
                  "tgt": np.random.RandomState(1).randn(3, 4)
                  .astype(np.float32)}


def _case_ragged():
    x = layers.data("x", [3], dtype="float32", lod_level=1)
    tgt = layers.data("tgt", [4], dtype="float32")
    h = layers.fc(x, size=4, act="tanh")       # ragged in, ragged out
    pooled = layers.sequence_pool(h, pool_type="sum")
    loss = layers.mean(layers.square(pooled - tgt))
    pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return loss, {"x": _ragged_floats(),
                  "tgt": np.random.RandomState(1).randn(3, 4)
                  .astype(np.float32)}


def _train(case, steps, iterations, seed=11):
    """Losses and every scope value after `steps` runs of a fresh build
    of `case` in a scope and an executor of its own."""
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.framework.isolated_name_scope():
        with pt.program_guard(main, startup):
            loss, feed = case()
    scope = pt.Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    losses = []
    with _counting() as c:
        for _ in range(steps):
            kw = {"iterations": iterations} if iterations > 1 else {}
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope, **kw)
            losses.append(np.asarray(lv))
    state = {n: np.asarray(scope.get(n))
             for n in sorted(scope.local_names()) if not n.startswith("@")}
    return losses, state, c.delta, main


@pytest.mark.parametrize("case,iterations,replays", [
    (_case_plain, 1, set()),
    (_case_snapshot, 1, {"increment"}),
    (_case_bounded_while, 1, {"while"}),
    (_case_dynamic_rnn_closure, 1, set()),
    (_case_ragged, 1, set()),
    (_case_plain, 4, set()),
], ids=["plain", "pre-snapshot", "while-max_steps", "dynamic_rnn-closure",
        "ragged-input", "iterations-4"])
def test_reuse_equals_replay_to_the_bit(case, iterations, replays,
                                        monkeypatch):
    got = _train(case, 3, iterations)
    with monkeypatch.context() as m:
        _replay_only(m)
        want = _train(case, 3, iterations)
    for a, b in zip(got[0], want[0]):
        assert a.tobytes() == b.tobytes()
    assert got[1].keys() == want[1].keys() and got[1]
    for name in got[1]:
        assert got[1][name].tobytes() == want[1][name].tobytes(), name
    # reuse served every site but the ones I3 excludes; replay none
    assert _served(want[2], "reused") == 0
    assert _served(got[2], "reused") > 0
    replayed = {op for (s, op), v in got[2].items() if s == "replayed"}
    assert replayed == replays
    if case is _case_snapshot:
        ops = got[3].desc.global_block.ops
        assert any("@PRE." in n for op in ops if op.type == "__vjp__"
                   for n in op.inputs["FwdIn"])
    if case is _case_dynamic_rnn_closure:
        assert _served(got[2], "reused", "dynamic_rnn") == 1


# ---------------------------------------------------------------------------
# (3) I1: a program without grad ops is traced as before
# ---------------------------------------------------------------------------
def _no_vjp(*a, **kw):
    raise AssertionError("jax.vjp entered while tracing a program "
                         "without grad ops")


def _drive_generation():
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationModel,
                                               GenerationSpec)
    model = GenerationModel.build(GenerationSpec(
        vocab_size=32, max_seq_len=16, slots=2, prompt_buckets=(8, 16),
        cache_buckets=(8, 16), n_layer=1, n_head=2, d_model=8, d_inner=16,
        seed=3, eos_id=1))
    eng = model.serve(config=GenerationConfig(max_new_tokens=6)).start()
    try:      # a prefill and decode steps across both cache buckets
        out = eng.submit([5, 9, 3, 2, 7]).result(timeout=120)
    finally:
        eng.stop(drain=True, timeout=120)
    assert out.tokens


def _drive_inference_model(tmp_path):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        label = layers.data("label", [1])
        pred = layers.fc(layers.fc(x, size=8, act="relu"), size=2)
        loss = layers.mean(layers.square(pred - label))
        pt.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    pt.io.save_inference_model(str(tmp_path), ["x"], [pred], exe,
                               main_program=main)
    return tmp_path


@pytest.mark.parametrize("what", ["generation", "inference_model"])
def test_program_without_grad_ops_traces_as_before(what, tmp_path,
                                                   monkeypatch):
    if what == "inference_model":
        _drive_inference_model(tmp_path)
    # no look-ahead result, no dict in `extra`, no jax.vjp, no count
    seen = []
    orig_trace_ops = executor_mod._trace_ops

    def spy(block, env, extra, sites):
        seen.append((sites, core_ops.VJP_PULLBACKS in extra))
        return orig_trace_ops(block, env, extra, sites)

    monkeypatch.setattr(executor_mod, "_trace_ops", spy)
    monkeypatch.setattr(jax, "vjp", _no_vjp)
    with _counting() as c:
        if what == "generation":
            _drive_generation()
        else:
            exe = pt.Executor()
            prog, feeds, fetches = pt.io.load_inference_model(
                str(tmp_path), exe)
            (out,) = exe.run(prog, feed={feeds[0]: np.ones((3, 4),
                                                           np.float32)},
                             fetch_list=fetches)
            assert np.asarray(out).shape == (3, 2)
    assert seen and all(s == (None, False) for s in seen)
    assert _served(c.delta, "reused") == 0
    assert _served(c.delta, "replayed") == 0


# ---------------------------------------------------------------------------
# (4) I2: a pullback belongs to one trace_block call
# ---------------------------------------------------------------------------
def _watch_pullbacks(monkeypatch):
    """Weak references to every pullback the trace makes, its jaxpr and
    the tracers it was linearised at, and to every `extra` it sat in."""
    refs, extras = [], []
    orig = core_ops.run_op_keeping_pullback

    def hook(op, gop, env, extra):
        outs = orig(op, gop, env, extra)
        kept = extra[core_ops.VJP_PULLBACKS].get(id(gop))
        if outs is not None and kept is not None:
            pullback, in_vals = kept
            refs.append(weakref.ref(pullback))
            refs.append(weakref.ref(pullback.jaxpr))
            refs.extend(weakref.ref(v) for v in
                        jax.tree_util.tree_leaves((in_vals, outs))
                        if isinstance(v, jax.core.Tracer))
            extras.append(extra)
        return outs

    monkeypatch.setattr(core_ops, "run_op_keeping_pullback", hook)
    return refs, extras


def _holds_trace_objects(root, limit=300000):
    """A pullback or a tracer reachable from `root` through gc-tracked
    references, or None. Modules, classes and a function's globals are
    not walked (they lead to all of JAX); the step's own jaxpr, which
    the jit cache keeps as on the parent, is no pullback: the jaxprs of
    the pullbacks are in the weak references."""
    import types
    bad = (jax.core.Tracer, type(jax.vjp(lambda x: x, 1.0)[1]))
    seen, todo = set(), [root]
    while todo and len(seen) < limit:
        obj = todo.pop()
        if id(obj) in seen or isinstance(obj, (type, types.ModuleType)):
            continue
        seen.add(id(obj))
        if isinstance(obj, bad):
            return obj
        if isinstance(obj, types.FunctionType):
            for cell in obj.__closure__ or ():
                try:
                    todo.append(cell.cell_contents)
                except ValueError:      # an empty cell
                    pass
            todo.extend(obj.__defaults__ or ())
            continue
        todo.extend(gc.get_referents(obj))
    return None


def test_no_pullback_outlives_its_trace(monkeypatch):
    refs, extras = _watch_pullbacks(monkeypatch)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        loss, feed = _case_plain()
    exe = pt.Executor()
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    assert refs and extras
    assert all(core_ops.VJP_PULLBACKS not in e for e in extras)
    del extras[:]
    gc.collect()
    assert [r for r in refs if r() is not None] == []
    for root in (main, main.desc, exe, exe._cache, vars(core_ops)):
        assert _holds_trace_objects(root) is None


def test_exception_in_mid_block_leaves_no_pullback(monkeypatch):
    refs, extras = _watch_pullbacks(monkeypatch)
    from paddle_tpu.core.registry import OpRegistry
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        loss, feed = _case_plain()
    exe = pt.Executor()
    exe.run(startup)

    class _Boom(Exception):
        pass

    def boom(ctx):
        raise _Boom("mid-block")

    # the loss's rule fails after the forward sites before it ran
    monkeypatch.setattr(OpRegistry.get("mean"), "compute", boom)
    with pytest.raises(Exception) as ei:
        exe.run(main, feed=feed, fetch_list=[loss])
    assert "mid-block" in str(ei.value)
    assert refs and extras
    assert all(core_ops.VJP_PULLBACKS not in e for e in extras)
    del ei, extras[:]
    gc.collect()
    assert [r for r in refs if r() is not None] == []
    for root in (main, main.desc, exe, vars(core_ops)):
        assert _holds_trace_objects(root) is None


def test_scan_body_keeps_its_own_pullbacks(monkeypatch):
    """iterations=4: the step is traced inside lax.scan (and once under
    eval_shape); each of those trace_block calls makes and consumes its
    own pullbacks, and none is left in `extra` between them."""
    depth = []
    orig = executor_mod._trace_ops

    def spy(block, env, extra, sites):
        if not sites:                  # the startup program
            return orig(block, env, extra, sites)
        kept = extra.get(core_ops.VJP_PULLBACKS)
        assert kept == {}, "a fresh dict for this call"
        env = orig(block, env, extra, sites)
        depth.append(len(kept))
        return env

    monkeypatch.setattr(executor_mod, "_trace_ops", spy)
    losses, state, delta, _ = _train(_case_plain, 1, 4)
    assert len(depth) >= 2          # eval_shape's trace and the scan's
    assert all(n == 0 for n in depth), "every pullback was consumed"
    assert _served(delta, "replayed") == 0


# ---------------------------------------------------------------------------
# (5) the counter
# ---------------------------------------------------------------------------
def test_counter_reads_the_attention_sites_of_a_transformer(monkeypatch):
    from paddle_tpu.models import transformer
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", "force")
    S, B = 128, 2
    main, startup, fetch = transformer.build_train(
        src_vocab=100, trg_vocab=100, max_len=S, n_layer=2, n_head=2,
        d_model=32, d_inner=64)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)

    def ids():
        return rng.randint(1, 100, (B, S, 1)).astype("int64")

    feed = {"src_ids": ids(), "trg_ids": ids(), "trg_labels": ids(),
            "pos_ids": np.arange(S).astype("int64")}
    with _counting() as c:
        exe.run(main, feed=feed, fetch_list=[fetch["loss"]])
    # 2 encoder self + 2 decoder self + 2 cross (the base model: 18)
    assert _served(c.delta, "reused", SDPA) == 6
    assert _served(c.delta, "replayed", SDPA) == 0
    calls = _kernel_calls(_step_jaxpr(exe, main))
    assert dict(calls) == dict(flash_fwd=6, flash_bwd_dkv_dq=6)
    # and so is every other site: the program is traced as built, so
    # each grad op embeds exactly the wiring its forward op has (output
    # names included) and no site of this model replays
    assert _served(c.delta, "replayed") == 0
    # (98 grad sites: a self-attention's q, k, v and a cross-attention's
    # k, v are ONE fan-out op a site since ISSUE 50, 6 where 16 `mul`s
    # stood, and their custom pullback is kept and applied like any other)
    assert _served(c.delta, "reused", "fanout_mul") == 6
    assert _served(c.delta, "reused") > 90


def test_counter_replays_snapshots_and_counts_no_probe(monkeypatch):
    """An unbounded While on the grad path: the probe traces a forward
    prefix with no `__vjp__` in it (nothing counted); the real trace
    feeds the While's grad op @PRE. snapshots of its carries, so that
    site replays, and every other site is reused."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.create_parameter(
            shape=[1], dtype="float32", name="xparam_reuse",
            default_initializer=pt.initializer.ConstantInitializer(0.3))
        thr = layers.data("thr", [1], dtype="float32")
        s = layers.fill_constant([1], "float32", 0.0)
        s.stop_gradient = False
        cond = cf.less_than_v(s, thr)
        w = cf.While(cond)
        with w.block():
            layers.assign(layers.elementwise_add(s, x), output=s)
            cf.less_than_v(s, thr, cond=cond)
        tgt = layers.fill_constant([1], "float32", 2.0)
        loss = layers.reduce_sum(layers.square(
            layers.elementwise_sub(s, tgt)))
        pt.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    probes = []
    orig = executor_mod._trace_ops

    def spy(block, env, extra, sites):
        if isinstance(block, executor_mod._BlockPrefix):
            probes.append(sites)
        return orig(block, env, extra, sites)

    monkeypatch.setattr(executor_mod, "_trace_ops", spy)
    with _counting() as c:
        (lv,) = exe.run(main, feed={"thr": np.asarray([1.0], np.float32)},
                        fetch_list=[loss])
    np.testing.assert_allclose(float(np.asarray(lv)), (1.2 - 2.0) ** 2,
                               rtol=1e-5)
    assert probes == [None]
    replayed = {op: v for (s_, op), v in c.delta.items() if s_ == "replayed"}
    assert replayed == {"while": 1}
    assert _served(c.delta, "reused") >= 3


# ---------------------------------------------------------------------------
# (6) the knob that chooses the kernel where the op leaves it open
# ---------------------------------------------------------------------------
def _knob_step(monkeypatch, knob, attrs, grad):
    """(kernel calls of the step, its loss) of one attention op carrying
    `attrs`, traced under PADDLE_TPU_PALLAS_SDPA=`knob`."""
    from paddle_tpu.layer_helper import LayerHelper
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", knob)
    pt.reset_default_programs()
    pt.reset_global_scope()
    H, S, D = _ATT["H"], _ATT["S"], _ATT["D"]
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 3
    with pt.program_guard(main, startup):
        q, k, v, label = (layers.data(n, [H, S, D])
                          for n in ("q", "k", "v", "label"))
        proj = [layers.fc(t, size=D, num_flatten_dims=3, bias_attr=False)
                for t in (q, k, v)]
        helper = LayerHelper("sdpa")
        out = helper.create_tmp_variable("float32")
        helper.append_op(type=SDPA, inputs=dict(zip("QKV", proj)),
                         outputs={"Out": out},
                         attrs=dict(attrs, causal=True))
        loss = layers.mean(layers.square(out - label))
        if grad:
            pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    (lv,) = exe.run(main, feed=_att_feed(), fetch_list=[loss])
    return dict(_kernel_calls(_step_jaxpr(exe, main))), float(lv)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "minimize"])
@pytest.mark.parametrize("attrs", [{}, {"use_flash": False},
                                   {"use_flash": True}],
                         ids=["attr-absent", "attr-False", "attr-True"])
@pytest.mark.parametrize("knob", ["1", "0", "force"])
def test_sdpa_knob_chooses_the_kernel(knob, attrs, grad, monkeypatch):
    """The step holds the flash kernels exactly when the op asks for
    them itself, or leaves the choice open and the knob says `force`
    (off the TPU `1` keeps the composition whatever the length); the
    op's own attr wins over the knob either way; and with a grad op the
    backward kernel comes with the forward one, because the grad op
    differentiates the same rule."""
    calls, loss = _knob_step(monkeypatch, knob, attrs, grad)
    want = {}
    if attrs.get("use_flash", knob == "force"):
        want = {"flash_fwd": 1}
        if grad:
            want.update(flash_bwd_dkv_dq=1)
    assert calls == want
    composed_calls, composed = _knob_step(
        monkeypatch, "0", {"use_flash": False}, grad)
    assert composed_calls == {}
    if want:
        np.testing.assert_allclose(loss, composed, rtol=1e-5)
    else:
        assert loss == composed
