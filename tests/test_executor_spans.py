"""The spans around a dispatch (core/executor.py, trainer.py): what a
compile-cache miss pays, phase by phase, and the executor's and the
Trainer's own host work around the jitted call."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.observability import default_registry
from paddle_tpu.trainer import Trainer

OWN_PHASES = ("verify", "memory_plan", "cost_model")
JAX_PHASES = ("jax_trace", "lower", "backend")


def _program(width=16):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        pred = layers.fc(layers.fc(x, size=width, act="relu"), size=1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(seed=0, batch=16):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(batch, 8).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}


class Heard:
    """Closed profiler events while the block runs."""

    def __enter__(self):
        self.events = []
        profiler.add_event_listener(self.events.append)
        return self

    def __exit__(self, *exc):
        profiler.remove_event_listener(self.events.append)

    def named(self, name, **args):
        return [e for e in self.events if e["name"] == name and all(
            e.get("args", {}).get(k) == v for k, v in args.items())]


def _outermost(events):
    """Of one name's events, those no other of them encloses: an inner
    jit fires its own compile event inside the outer one's."""
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in events]
    return [e for e, (s, t) in zip(events, spans)
            if not any(a <= s and t <= b and (a, b) != (s, t)
                       for a, b in spans)]


def _phase_seconds():
    fam = default_registry().get("paddle_tpu_compile_phase_seconds_total")
    return {} if fam is None else {k[0]: c.value for k, c in fam.samples()}


@pytest.fixture
def started():
    main, startup, loss = _program()
    exe = pt.Executor()
    exe.run(startup)
    return exe, main, loss


@pytest.mark.parametrize("phase", OWN_PHASES + JAX_PHASES)
def test_a_compile_miss_closes_each_phase_once_and_a_hit_none(started,
                                                              phase):
    exe, main, loss = started
    uid = main.desc.uid
    before = _phase_seconds().get(phase, 0.0)
    with Heard() as miss:
        exe.run(main, feed=_feed(), fetch_list=[loss])
    mine = miss.named("compile::" + phase, uid=uid, block=0)
    assert len(_outermost(mine)) == 1, [e["args"] for e in mine]
    assert all(e["cat"] == profiler.CAT_COMPILE for e in mine)
    # the counter took the union, not the sum, of nested events
    counted = _phase_seconds()[phase] - before
    outer = _outermost(miss.named("compile::" + phase))
    assert counted == pytest.approx(
        sum(e["dur"] for e in outer) * 1e-6, rel=1e-6, abs=1e-9)
    assert counted <= sum(e["dur"] for e in mine) * 1e-6 + 1e-9 \
        or len(mine) == 1
    with Heard() as hit:
        exe.run(main, feed=_feed(1), fetch_list=[loss])
    assert not [e for e in hit.events
                if e["name"].startswith("compile::")]
    assert _phase_seconds()[phase] - before == pytest.approx(counted)


def test_jax_phases_lie_inside_the_first_dispatch(started):
    exe, main, loss = started
    with Heard() as h:
        exe.run(main, feed=_feed(), fetch_list=[loss])
    (dispatch,) = h.named("pipeline::dispatch")
    lo, hi = dispatch["ts"], dispatch["ts"] + dispatch["dur"]
    for phase in JAX_PHASES:
        for e in h.named("compile::" + phase, uid=main.desc.uid):
            # an emitted span ends "now" on the listener's clock
            assert lo - 1e3 <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e3
    (prepare,) = h.named("pipeline::prepare")
    for phase in ("memory_plan", "cost_model"):
        (e,) = h.named("compile::" + phase)
        assert prepare["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= prepare["ts"] + prepare["dur"]


@pytest.mark.parametrize("sync", [True, False])
def test_prepare_dispatch_commit_nest_in_order_in_one_step(started, sync):
    from paddle_tpu.observability import trace as obs_trace
    exe, main, loss = started
    exe.run(main, feed=_feed(), fetch_list=[loss])      # compile
    with Heard() as h:
        with obs_trace.step_trace(7):
            out = exe.run(main, feed=_feed(), fetch_list=[loss],
                          sync=sync)
            out = out if sync else out.fetches()
    assert np.isfinite(out[0]).all()
    (root,) = h.named("trace::step/7")
    names = ["pipeline::prepare", "pipeline::dispatch",
             "pipeline::commit", "pipeline::fetch_sync"]
    spans = [h.named(n) for n in names]
    assert [len(s) for s in spans] == [1, 1, 1, 1]
    edges = [(s[0]["ts"], s[0]["ts"] + s[0]["dur"]) for s in spans]
    assert root["ts"] <= edges[0][0]
    for (_a, end), (start, _b) in zip(edges, edges[1:]):
        assert end <= start           # in order, none inside another
    assert edges[-1][1] <= root["ts"] + root["dur"]
    # one trace id for the whole step, each span on this thread
    ids = {s[0]["args"]["trace_id"] for s in spans}
    assert ids == {root["args"]["trace_id"]}
    assert {s[0]["tid"] for s in spans} == {root["tid"]}


def _covered(root, children):
    lo, hi = root["ts"], root["ts"] + root["dur"]
    edges = sorted((max(lo, c["ts"]), min(hi, c["ts"] + c["dur"]))
                   for c in children)
    total, cur = 0.0, lo
    for s, e in edges:
        if e > max(s, cur):
            total += e - max(s, cur)
            cur = e
    return total / root["dur"]


@pytest.mark.parametrize("prefetch", [0, 2])
def test_a_trainer_step_is_covered_by_its_children(prefetch):
    # wide enough that a step is milliseconds: the seams between spans
    # are microseconds each whatever the step does
    main, startup, loss = _program(width=4096)
    trainer = Trainer(loss, main_program=main, startup_program=startup)
    seen = []

    def handler(ev):
        seen.append(type(ev).__name__)

    def reader():
        for i in range(12):
            yield _feed(i, batch=2048)

    with Heard() as h:
        trainer.train(num_passes=1, reader=reader, event_handler=handler,
                      prefetch=prefetch, log_every=1)
    roots = [e for e in h.events if e["name"].startswith("trace::step/")]
    assert len(roots) == 12
    handlers = h.named("trainer::handler")
    assert len(handlers) == len(seen) == 2 + 2 * 12
    assert len(h.named("trainer::telemetry")) == 12
    assert all(e["cat"] == profiler.CAT_TRAINER for e in handlers)
    shares = []
    for root in roots[2:]:          # past the compiling steps
        inside = [e for e in h.events if e is not root
                  and e["tid"] == root["tid"]
                  and root["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= root["ts"] + root["dur"]]
        shares.append(_covered(root, inside))
    # a scheduler stall may land in one step's seams; not in most
    assert sorted(shares)[len(shares) // 2] >= 0.9, shares
    # telemetry runs after the root span, before the next one
    tele = h.named("trainer::telemetry")
    for t, root in zip(tele, roots):
        assert t["ts"] >= root["ts"] + root["dur"] - 1.0


def test_ops_are_named_scopes_in_the_lowered_step(started):
    import jax
    exe, main, loss = started
    exe.run(main, feed=_feed(), fetch_list=[loss])
    (compiled,) = [c for k, c in exe._cache.items()
                   if k[0] == main.desc.uid]
    feed = {k: jax.numpy.asarray(v) for k, v in _feed().items()}
    scope = pt.global_scope()
    ro = {n: scope.get(n) for n in compiled.ro_names}
    rw = {n: scope.get(n) for n in compiled.rw_names}
    text = compiled.jitted.lower(
        feed, ro, rw, jax.numpy.zeros((), "int32")).as_text(
        debug_info=True)
    for op_type in ("mul", "sgd", "__vjp__.mul"):
        assert f"/{op_type}/" in text or f"/{op_type}\"" in text, op_type
