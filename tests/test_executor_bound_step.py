"""The bound step (core/executor.py `_Binding`): `Executor.run` resolves
a program as it is called, and a feed signature under it, ONCE; every
later call of that shape is a look-up, the state's handles read from
the scope by name, and the jitted call. What stays as it was: the scope
is the one source of truth (arrays are never kept across calls), a
moved version, a new feed shape, a flipped PADDLE_TPU_VERIFY or AMP
state bind anew, a dynamic-While-gradient program probes on every call.
"""
import threading

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.core.executor as ex_mod
from paddle_tpu import layers, profiler
from paddle_tpu.core.executor import STEP_VAR
from paddle_tpu.core.scope import Scope
from paddle_tpu.layers import control_flow as cf
from paddle_tpu.observability import default_registry

import test_while_grad_dynamic as dynamic_while


# -- light programs ----------------------------------------------------------

def _train(width=8):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(layers.fc(x, size=width, act="relu"), size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _bounded_while():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        lim = layers.data("lim", [1], dtype="float32")
        s = layers.fill_constant([1], "float32", 0.0)
        cond = cf.less_than_v(s, lim)
        w = cf.While(cond, max_steps=16)
        with w.block():
            layers.increment(s, value=1.0, in_place=True)
            cf.less_than_v(s, lim, cond=cond)
    return main, startup, s


def _cache_pair():
    """A prefill and a decode program over ONE persistable `cache`, as
    a token server's: prefill adds its feed into the cache, decode
    doubles it; each fetches the cache's sum after its write."""
    startup = pt.Program()
    programs = []
    for mode in ("prefill", "decode"):
        main = pt.Program()
        with pt.program_guard(main, startup):
            cache = main.global_block().create_var(
                name="cache", shape=[4], dtype="float32",
                persistable=True)
            if mode == "prefill":
                x = layers.data("x", [4], dtype="float32",
                                append_batch_size=False)
                new = layers.elementwise_add(cache, x)
            else:
                new = layers.scale(cache, scale=2.0)
            layers.assign(new, output=cache)
            total = layers.reduce_sum(new)
        programs.append((main, total))
    with pt.program_guard(pt.Program(), startup):
        layers.create_global_var([4], 0.0, "float32", persistable=True,
                                 name="cache")
    return startup, programs[0], programs[1]


def _feed(batch=8, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(batch, 4).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}


def _bound_counts():
    fam = default_registry().get("paddle_tpu_executor_bound_steps_total")
    got = {} if fam is None else {k[0]: c.value for k, c in fam.samples()}
    return got.get("bound", 0), got.get("hit", 0)


class CountingOps(list):
    """A block's `ops` that counts every walk of it."""

    walks = 0

    def __iter__(self):
        CountingOps.walks += 1
        return super().__iter__()

    def __getitem__(self, i):
        CountingOps.walks += 1
        return super().__getitem__(i)


def _count_walks(program):
    CountingOps.walks = 0
    for block in program.desc.blocks:
        block.ops = CountingOps(block.ops)


class Heard:
    def __enter__(self):
        self.events = []
        profiler.add_event_listener(self.events.append)
        return self

    def __exit__(self, *exc):
        profiler.remove_event_listener(self.events.append)

    def named(self, name):
        return [e for e in self.events if e["name"] == name]


# -- a hit does no work in the program's ops ---------------------------------

def _started(kind):
    if kind == "train":
        main, startup, fetch = _train()
        feed = _feed()
    elif kind == "bounded_while":
        main, startup, fetch = _bounded_while()
        feed = {"lim": np.asarray([3.0], np.float32)}
    else:
        startup, (main, fetch), _decode = _cache_pair()
        feed = {"x": np.ones((4,), np.float32)}
    exe = pt.Executor()
    exe.run(startup)
    return exe, main, fetch, feed


@pytest.mark.parametrize("kind", ["train", "bounded_while", "state"])
def test_a_hit_walks_no_block_of_the_program(kind):
    exe, main, fetch, feed = _started(kind)
    _count_walks(main)   # the blocks keep their ops, in lists that count
    exe.run(main, feed=feed, fetch_list=[fetch])
    assert CountingOps.walks > 0        # the bind and the trace walked
    CountingOps.walks = 0
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[fetch])
    assert CountingOps.walks == 0


@pytest.mark.parametrize("scan", ["dynamic_while_targets", "flags"])
def test_the_scans_run_once_a_program_version(monkeypatch, scan):
    exe, main, fetch, feed = _started("bounded_while")
    calls = []
    if scan == "dynamic_while_targets":
        real = ex_mod._dynamic_while_targets
        monkeypatch.setattr(
            ex_mod, "_dynamic_while_targets",
            lambda block: calls.append(block) or real(block))
    else:
        real = ex_mod._Binding

        class Spy(real):
            __slots__ = ()

            def __init__(self, block, user_fetches):
                calls.append(block)
                super().__init__(block, user_fetches)
        monkeypatch.setattr(ex_mod, "_Binding", Spy)
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[fetch])
    assert len(calls) == 1
    with pt.program_guard(main):        # the version moves
        layers.scale(fetch, scale=2.0)
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[fetch])
    assert len(calls) == 2


def test_a_hit_launches_nothing_but_the_step(monkeypatch):
    """No `jnp` call of the executor's own on a hit (no feed through
    `jnp.asarray`, no eager `step + 1`): the counter the scope holds
    after the call IS the array the compiled step returned."""
    exe, main, loss, feed = _started("train")
    exe.run(main, feed=feed, fetch_list=[loss])
    (compiled,) = next(b for k, b in exe._bindings.items()
                       if k[0] == main.desc.uid).steps.values()
    returned = []
    fn = compiled.fn

    def heard(*args):
        out = fn(*args)
        returned.append(out[2])
        return out
    compiled.fn = heard

    class NoJnp:
        def __getattr__(self, name):
            raise AssertionError(f"jnp.{name} on a hit")
    monkeypatch.setattr(ex_mod, "jnp", NoJnp())
    before = int(np.asarray(pt.global_scope().get(STEP_VAR)))
    exe.run(main, feed=feed, fetch_list=[loss])
    assert pt.global_scope().get(STEP_VAR) is returned[0]
    assert int(np.asarray(returned[0])) == before + 1


# -- the scope stays the one source of truth ---------------------------------

@pytest.mark.parametrize("donate", [True, False],
                         ids=["donated", "copied"])
def test_two_programs_alternate_over_one_scopes_state(donate):
    startup, (prefill, p_sum), (decode, d_sum) = _cache_pair()
    scope = Scope()
    exe = pt.Executor(donate_state=donate)
    exe.run(startup, scope=scope)
    want = np.zeros(4, np.float32)
    for i in range(4):
        x = np.full((4,), float(i + 1), np.float32)
        want = want + x
        (got,) = exe.run(prefill, feed={"x": x}, fetch_list=[p_sum],
                         scope=scope)
        assert float(got) == want.sum()
        for _ in range(2):
            want = want * 2
            (got,) = exe.run(decode, fetch_list=[d_sum], scope=scope)
            assert float(got) == want.sum()
    np.testing.assert_array_equal(np.asarray(scope.get("cache")), want)
    assert exe.cache_stats == {"misses": 3, "hits": 10}


def test_a_write_from_outside_is_what_the_next_call_reads():
    startup, _prefill, (decode, d_sum) = _cache_pair()
    exe = pt.Executor()
    exe.run(startup)
    scope = pt.global_scope()
    exe.run(decode, fetch_list=[d_sum])
    # a checkpoint restore, a weight swap: state written between steps
    scope.set("cache", np.asarray([1, 2, 3, 4], np.float32))
    (got,) = exe.run(decode, fetch_list=[d_sum])
    assert float(got) == 20.0
    scope.set(STEP_VAR, np.int32(41))
    exe.run(decode, fetch_list=[d_sum])
    assert int(np.asarray(scope.get(STEP_VAR))) == 42


def test_a_child_scope_shadows_its_parent_and_reads_through_it():
    main, startup, loss = _train()
    parent = Scope()
    exe = pt.Executor()
    exe.run(startup, scope=parent)
    child = parent.new_scope()
    feed = _feed()
    (first,) = exe.run(main, feed=feed, fetch_list=[loss], scope=child)
    # the step read the parent's parameters and wrote its own: the
    # parent's are donated away, the child's shadow them from now on
    weights = [n for n in child.local_names() if not n.startswith("@")]
    assert weights
    (second,) = exe.run(main, feed=feed, fetch_list=[loss], scope=child)
    assert float(second) < float(first)
    # a name the child holds wins over the parent's
    name = weights[0]
    parent.set(name, np.full(np.shape(child.get(name)), np.nan, np.float32))
    (third,) = exe.run(main, feed=feed, fetch_list=[loss], scope=child)
    assert np.isfinite(float(third))


def test_a_donated_name_the_trace_does_not_write_back_is_erased(
        monkeypatch):
    startup, _prefill, (decode, d_sum) = _cache_pair()
    exe = pt.Executor(donate_state=True)
    exe.run(startup)
    compile_ = exe._compile

    def forgetful(*args, **kw):
        compiled = compile_(*args, **kw)
        fn = compiled.fn

        def call(*a):
            fetches, new_state, step = fn(*a)
            new_state.pop("cache")
            return fetches, new_state, step
        compiled.fn = call
        return compiled
    monkeypatch.setattr(exe, "_compile", forgetful)
    exe.run(decode, fetch_list=[d_sum])
    with pytest.raises(KeyError, match="cache"):
        pt.global_scope().get("cache")
    with pytest.raises(KeyError, match="cache"):    # a bound step too
        exe.run(decode, fetch_list=[d_sum])


def test_an_async_fetch_of_donated_state_raises_on_every_call():
    startup, _prefill, (decode, _d_sum) = _cache_pair()
    exe = pt.Executor(donate_state=True)
    exe.run(startup)
    for _ in range(2):
        with pytest.raises(ValueError, match="donated state"):
            exe.run(decode, fetch_list=["cache"], sync=False)
    (got,) = exe.run(decode, fetch_list=["cache"])      # sync may
    assert got.shape == (4,)


# -- what binds anew ----------------------------------------------------------

def test_a_mutated_program_binds_anew_and_runs_the_new_ops():
    exe, main, s, feed = _started("bounded_while")
    (before,) = exe.run(main, feed=feed, fetch_list=[s])
    assert float(before[0]) == 3.0
    bound0, _ = _bound_counts()
    with pt.program_guard(main):
        layers.increment(s, value=10.0, in_place=True)
    (after,) = exe.run(main, feed=feed, fetch_list=[s])
    assert float(after[0]) == 13.0
    assert _bound_counts()[0] == bound0 + 1


def test_a_new_feed_shape_binds_anew_and_the_old_one_still_hits():
    exe, main, loss, _ = _started("train")
    small, large = _feed(batch=8), _feed(batch=16)
    exe.run(main, feed=small, fetch_list=[loss])
    bound0, hit0 = _bound_counts()
    exe.run(main, feed=large, fetch_list=[loss])
    assert _bound_counts() == (bound0 + 1, hit0)
    exe.run(main, feed=small, fetch_list=[loss])
    exe.run(main, feed=large, fetch_list=[loss])
    assert _bound_counts() == (bound0 + 1, hit0 + 2)
    assert exe.cache_stats["misses"] == 3        # startup and two shapes


@pytest.mark.parametrize("what", ["verify", "amp"])
def test_a_flipped_switch_binds_anew(monkeypatch, what):
    exe, main, loss, feed = _started("train")
    exe.run(main, feed=feed, fetch_list=[loss])
    exe.run(main, feed=feed, fetch_list=[loss])
    bound0, hit0 = _bound_counts()
    if what == "verify":
        monkeypatch.setenv("PADDLE_TPU_VERIFY", "0")
        exe.run(main, feed=feed, fetch_list=[loss])
    else:
        with pt.amp.amp_guard(True):
            exe.run(main, feed=feed, fetch_list=[loss])
    assert _bound_counts() == (bound0 + 1, hit0)
    exe.run(main, feed=feed, fetch_list=[loss])   # the first record's
    assert _bound_counts() == (bound0 + 1, hit0 + 1)


@pytest.mark.parametrize("dtype", ["int64", "int32"])
def test_a_host_feed_keys_by_the_dtype_it_has_on_the_device(dtype):
    """int64 from the host is int32 on the device (x64 off): the two
    are one compiled step, as when every feed went through
    `jnp.asarray`, and the compile key reads int32."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", [4], dtype="int64",
                          append_batch_size=False)
        out = layers.reduce_sum(layers.cast(ids, "float32"))
    exe = pt.Executor()
    host = np.arange(4, dtype=dtype)
    (got,) = exe.run(main, feed={"ids": host}, fetch_list=[out])
    assert float(got) == 6.0
    import jax.numpy as jnp
    exe.run(main, feed={"ids": jnp.asarray(host)}, fetch_list=[out])
    assert exe.cache_stats == {"misses": 1, "hits": 1}
    (key,) = exe._cache
    assert key[2] == (("ids", ((4,), "int32")),)
    assert ex_mod.feed_signature({"ids": host}) == key[2]


def test_a_dynamic_while_gradient_program_probes_on_every_call(
        monkeypatch):
    main, startup, f = dynamic_while._build(lr=0.0)
    exe = pt.Executor()
    exe.run(startup)
    scans, probes = [], []
    real = ex_mod._dynamic_while_targets
    monkeypatch.setattr(ex_mod, "_dynamic_while_targets",
                        lambda block: scans.append(1) or real(block))
    probe = exe._probe_while_bounds
    monkeypatch.setattr(
        exe, "_probe_while_bounds",
        lambda *a: probes.append(1) or probe(*a))
    # thr 1.0 -> 4 trips, 2.0 -> 7 (bucket 8): the count is the feed's
    for thr, want in ((1.0, 4), (1.0, 4), (2.0, 7), (1.0, 4)):
        _, steps = exe.run(
            main, feed={"thr": np.asarray([thr], np.float32)},
            fetch_list=[f["loss"], f["w"].steps])
        assert int(np.asarray(steps)) == want
    assert len(probes) == 4 and len(scans) == 1
    assert exe.cache_stats["misses"] == 3    # startup and two buckets


# -- threads, the mesh executor ----------------------------------------------

def test_two_threads_share_one_executor():
    exe = pt.Executor()
    errors, results = [], {}
    # the same programs from two threads at once, each its own scope
    startup, (prefill, p_sum), (decode, d_sum) = _cache_pair()
    scopes = [Scope(), Scope()]
    for sc in scopes:
        exe.run(startup, scope=sc)
    go = threading.Barrier(2)

    def hammer(i):
        try:
            go.wait()
            x = np.full((4,), float(i + 1), np.float32)
            exe.run(prefill, feed={"x": x}, fetch_list=[p_sum],
                    scope=scopes[i])
            for _ in range(20):
                (got,) = exe.run(decode, fetch_list=[d_sum],
                                 scope=scopes[i])
            results[i] = float(got)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
    threads = [threading.Thread(target=hammer, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert results == {0: 4.0 * 2 ** 20, 1: 8.0 * 2 ** 20}


@pytest.mark.parametrize("lifted", [False, True],
                         ids=["one-process", "state-lifted"])
def test_the_mesh_executor_takes_hits(lifted):
    """ParallelExecutor inherits the bound step (it overrides `_compile`
    and `run` alone). `state-lifted` runs its multi-process path on
    this process's virtual devices: the read-only state it lifts to the
    mesh still reaches the run-time scope through `run`."""
    import jax
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.executor import ParallelExecutor, ShardingSpec

    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    startup, (prefill, p_sum), _decode = _cache_pair()
    with pt.program_guard(prefill, startup):
        gain = layers.create_parameter(
            [4], "float32", name="gain",
            default_initializer=pt.initializer.ConstantInitializer(3.0))
        out = layers.reduce_sum(layers.elementwise_mul(
            prefill.global_block().var("cache"), gain))
    pt.Executor().run(startup)
    exe = ParallelExecutor(mesh=mesh, sharding=ShardingSpec(
        specs={"x": jax.sharding.PartitionSpec()}))
    exe._multiprocess = lifted
    scope = pt.global_scope()
    assert not isinstance(scope.get("gain").sharding,
                          jax.sharding.NamedSharding)
    bound0, hit0 = _bound_counts()
    x = np.ones((4,), np.float32)
    for i in range(3):
        (got,) = exe.run(prefill, feed={"x": x}, fetch_list=[out])
        assert float(got) == 3.0 * 4 * (i + 1)
    assert _bound_counts() == (bound0 + 1, hit0 + 2)
    if lifted:
        sharding = scope.get("gain").sharding
        assert isinstance(sharding, jax.sharding.NamedSharding)
        assert sharding.mesh.shape == {"data": 4}


# -- the counter and the spans -----------------------------------------------

def test_the_counter_reads_bound_once_and_hit_after():
    exe, main, loss, feed = _started("train")
    bound0, hit0 = _bound_counts()
    for i in range(4):
        exe.run(main, feed=feed, fetch_list=[loss])
        assert _bound_counts() == (bound0 + 1, hit0 + i)


@pytest.mark.parametrize("span", ["prepare", "dispatch", "commit"])
def test_the_pipeline_spans_open_once_a_call(span):
    exe, main, loss, feed = _started("train")
    outcomes = []
    for _ in range(3):
        with Heard() as heard:
            exe.run(main, feed=feed, fetch_list=[loss])
        (event,) = heard.named("pipeline::" + span)
        outcomes.append(event["args"].get("bound"))
    if span == "prepare":
        assert outcomes == ["bound", "hit", "hit"]
    else:
        assert outcomes == [None] * 3
