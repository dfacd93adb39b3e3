"""The compile path holds one program object: the program the caller
built is the program the executor verifies, plans, traces and costs
(core/executor.py, miss branch of `_prepare`). With no clone anywhere
on that path a mutation there would land on the caller's object, and
`Program.clone()` — which still serves `for_test`, `io` pruning and the
serving hosts — has no caller on the training path any more; both are
held here over the nine lint networks (tools/lint_ir.py), which cover
While, StaticRNN, DynamicRNN and IfElse sub-blocks."""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.lod import LoDTensor

NETWORK_NAMES = ["fc_regression", "mnist_mlp", "mnist_conv", "seq_pool",
                 "embedding_lm", "while_loop", "static_rnn", "dynamic_rnn",
                 "ifelse"]


def _networks():
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from lint_ir import NETWORKS
    return NETWORKS


def _feed(name):
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
    if name in ("while_loop", "ifelse"):
        return {"x": rng.rand(2, 4).astype(np.float32)}
    if name == "static_rnn":
        return {"x": rng.rand(5, 4, 8).astype(np.float32)}
    if name == "dynamic_rnn":
        return {"sent": LoDTensor(rng.rand(5, 8).astype(np.float32),
                                  [[0, 2, 5]])}
    raise KeyError(name)


def _train_losses(main, startup, loss, feed, steps=3):
    """`steps` losses of (main, startup) in a scope and an executor of
    their own."""
    scope, exe = pt.Scope(), pt.Executor()
    with pt.scope_guard(scope):
        exe.run(startup)
        return [float(np.ravel(np.asarray(
            exe.run(main, feed=feed, fetch_list=[loss])[0]))[0])
            for _ in range(steps)]


def _frozen(program):
    return (json.dumps(program.desc.to_dict(), sort_keys=True),
            program.desc.version)


@pytest.mark.parametrize("name", NETWORK_NAMES)
def test_run_leaves_the_callers_program_untouched(name):
    main, startup, _feeds, fetches = _networks()[name]()
    before = _frozen(main), _frozen(startup)
    losses = _train_losses(main, startup, fetches[0], _feed(name))
    assert np.isfinite(losses).all()
    assert (_frozen(main), _frozen(startup)) == before


@pytest.mark.parametrize("name", NETWORK_NAMES)
def test_a_clone_trains_to_the_same_losses(name):
    main, startup, _feeds, fetches = _networks()[name]()
    feed = _feed(name)
    own = _train_losses(main, startup, fetches[0], feed)
    cloned_main, cloned_startup = main.clone(), startup.clone()
    assert cloned_main.desc is not main.desc
    assert cloned_main.desc.uid != main.desc.uid
    assert _train_losses(cloned_main, cloned_startup, fetches[0],
                         feed) == own
