#!/usr/bin/env python
"""lint_ir: run the static ProgramDesc verifier (or the cost model)
from the command line.

Two input modes:

  python tools/lint_ir.py <saved_inference_model_dir>
      Load a `save_inference_model` directory (program + params) into a
      private scope and verify the frozen program.

  python tools/lint_ir.py --network mnist_mlp
      Build one of the named test networks (the same graph shapes the
      test suite exercises) and verify its (main, startup) pair —
      including uninitialized-persistable detection, which needs both.

Either mode also supports --cost: instead of verifying, print the
static cost-model table (per-op FLOPs / bytes accessed / parameter
bytes plus program totals, analysis/cost_model.py) — offline
attribution with no step executed. --batch binds dynamic (-1) dims;
--json emits the machine-readable form.

Exit status: 0 when the verifier finds no error-severity diagnostics,
1 when it does (warnings never fail the lint; --strict promotes them).
--cost always exits 0 unless the model cannot be loaded/built.
tests/test_lint_cli.py drives every named network through this tool so
CI keeps the suite's programs verifier-clean.
"""
from __future__ import annotations

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _build_fc_regression():
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [13])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square(pred - y))
        optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    return main, startup, ["x", "y"], [loss.name]


def _build_mnist(net: str):
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    from paddle_tpu.models import mnist
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        shape = [784] if net == "mlp" else [1, 28, 28]
        img = layers.data("img", shape)
        label = layers.data("label", [1], dtype="int64")
        fn = mnist.mlp if net == "mlp" else mnist.conv_net
        _pred, loss, acc = fn(img, label)
        optimizer.AdamOptimizer(learning_rate=0.001).minimize(loss)
    return main, startup, ["img", "label"], [loss.name, acc.name]


def _build_seq_pool():
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        seq = layers.data("seq", [16], lod_level=1)
        y = layers.data("y", [1])
        h = layers.fc(seq, size=16, act="tanh")
        pooled = layers.sequence_pool(h, "sum")
        loss = layers.mean(layers.square(layers.fc(pooled, size=1) - y))
        optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    return main, startup, ["seq", "y"], [loss.name]


def _build_embedding_lm():
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        words = layers.data("words", [1], dtype="int64", lod_level=1)
        label = layers.data("label", [1], dtype="int64")
        emb = layers.embedding(words, size=[100, 16])
        pooled = layers.sequence_pool(emb, "sum")
        pred = layers.fc(pooled, size=100, act="softmax")
        loss = layers.mean(layers.cross_entropy(input=pred, label=label))
        optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    return main, startup, ["words", "label"], [loss.name]


def _build_while_loop():
    import paddle_tpu as pt
    from paddle_tpu import layers
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
    return main, startup, ["x"], [out.name]


def _build_static_rnn():
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        # [T, B, D]: the executable StaticRNN shape regime (a 1-D [D]
        # step input would make fc size its weight [1, D] at build
        # time, so the network could verify but never run —
        # tests/test_compile_path.py executes every network)
        x = layers.data("x", [5, 4, 8], append_batch_size=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(x)
            mem = rnn.memory(shape=[4, 8], value=0.0)
            nh = layers.fc(layers.elementwise_add(xt, mem), size=8,
                           act="tanh")
            rnn.update_memory(mem, nh)
            rnn.step_output(nh)
        out = layers.mean(rnn())
    return main, startup, ["x"], [out.name]


def _build_dynamic_rnn():
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        sent = layers.data("sent", [8], lod_level=1)
        drnn = layers.DynamicRNN()
        with drnn.block():
            wd = drnn.step_input(sent)
            mem = drnn.memory(shape=[8], value=0.0)
            nh = layers.fc(layers.elementwise_add(wd, mem), size=8,
                           act="tanh")
            drnn.update_memory(mem, nh)
            drnn.output(nh)
        last = layers.sequence_last_step(drnn())
        out = layers.mean(layers.fc(last, size=1))
    return main, startup, ["sent"], [out.name]


def _build_ifelse():
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        cond = layers.less_than(
            layers.mean(x), layers.fill_constant([1], "float32", 0.5))
        ie = layers.IfElse(cond)
        with ie.true_block():
            ie.output(layers.elementwise_add(x, x))
        with ie.false_block():
            ie.output(layers.elementwise_sub(x, x))
        out = layers.mean(ie())
    return main, startup, ["x"], [out.name]


def _build_deepfm_distributed():
    """DeepFM with is_distributed=True lookup tables — the IR program
    a sharded-embedding (paddle_tpu.embedding) deployment exports and
    serves; keeps the sharded-lookup op surface verifier-clean."""
    from paddle_tpu.models.deepfm import build_train
    main, startup, f = build_train(num_features=1000, num_fields=5,
                                   embed_dim=4, distributed=True)
    return main, startup, ["feat_ids", "feat_vals", "label"], \
        [f["loss"].name, f["pred"].name]


def _build_decoder_lm_step():
    """The token-serving decode-step program: single-token forward
    reading/writing the persistable KV cache through the donated
    kv_cache_append ops (models/transformer.py build_decoder_lm)."""
    from paddle_tpu.models.transformer import build_decoder_lm
    programs = build_decoder_lm(
        vocab_size=64, max_seq_len=16, slots=2, prompt_buckets=(8, 16),
        cache_buckets=(8, 16), n_layer=1, n_head=2, d_model=16,
        d_inner=32, seed=0)
    lm = programs["decode"][16]
    return lm.main, lm.startup, lm.feed_names, [lm.fetch_name]


#: name -> builder returning (main, startup, feed_names, fetch_names).
#: These mirror the network shapes the test suite runs (fc regression,
#: the mnist book nets, sequence/lod pipelines, every control-flow
#: construct, and the token-serving decode step) —
#: tests/test_lint_cli.py keeps each verifier-clean.
NETWORKS = {
    "fc_regression": _build_fc_regression,
    "mnist_mlp": lambda: _build_mnist("mlp"),
    "mnist_conv": lambda: _build_mnist("conv"),
    "seq_pool": _build_seq_pool,
    "embedding_lm": _build_embedding_lm,
    "while_loop": _build_while_loop,
    "static_rnn": _build_static_rnn,
    "dynamic_rnn": _build_dynamic_rnn,
    "ifelse": _build_ifelse,
    "decoder_lm_step": _build_decoder_lm_step,
    "deepfm_distributed": _build_deepfm_distributed,
}


def lint_network(name: str, retrace: bool = True):
    """Build the named network and verify it. Returns a VerifyReport."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis.passes import fast_passes
    main, startup, feeds, fetches = NETWORKS[name]()
    passes = None if retrace else fast_passes(with_uninit=True)
    return analysis.verify_program(
        main, startup=startup, feed_names=feeds, fetch_names=fetches,
        passes=passes, program_label=f"network {name!r}")


def _load_model_dir(dirname: str):
    """Load a save_inference_model directory into a private scope (the
    process global scope is untouched); returns (program, feed names,
    fetch names)."""
    import paddle_tpu as pt
    from paddle_tpu import io

    scope = pt.Scope()
    exe = pt.Executor()
    with pt.scope_guard(scope):
        prog, feed_names, fetch_vars, _meta = io.load_inference_model(
            dirname, exe, return_meta=True)
    return prog, feed_names, [v.name for v in fetch_vars]


def lint_model_dir(dirname: str):
    """Load a save_inference_model directory and verify the frozen
    program."""
    from paddle_tpu import analysis
    prog, feed_names, fetch_names = _load_model_dir(dirname)
    return analysis.verify_program(
        prog, feed_names=feed_names, fetch_names=fetch_names,
        program_label=f"model dir {dirname!r}")


def cost_report(network: str = None, model_dir: str = None,
                batch: int = 1):
    """Build/load the target program and return its ProgramCost."""
    from paddle_tpu.analysis import cost_model
    if network:
        main, _startup, _feeds, _fetches = NETWORKS[network]()
        prog, label = main, f"network {network!r}"
    else:
        prog, _feeds, _fetches = _load_model_dir(model_dir)
        label = f"model dir {model_dir!r}"
    return cost_model.program_cost(prog, batch=batch, label=label)


def memory_report(network: str = None, model_dir: str = None,
                  batch: int = 1):
    """Build/load the target program and return its MemoryReport
    (analysis/memory.py): liveness intervals, peak-HBM estimate,
    high-water op, top live tensors."""
    from paddle_tpu.analysis import memory
    if network:
        main, _startup, feeds, _fetches = NETWORKS[network]()
        prog, label = main, f"network {network!r}"
    else:
        prog, feeds, _fetches = _load_model_dir(model_dir)
        label = f"model dir {model_dir!r}"
    return memory.program_memory(prog, batch=batch, feed_names=feeds,
                                 label=label)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="lint_ir",
        description="Static ProgramDesc verifier (paddle_tpu.analysis) "
                    "over a saved inference model or a named test "
                    "network.")
    ap.add_argument("model_dir", nargs="?",
                    help="save_inference_model directory to verify")
    ap.add_argument("--network", choices=sorted(NETWORKS),
                    help="build + verify a named test network instead")
    ap.add_argument("--list-networks", action="store_true",
                    help="print the known network names and exit")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress warning/info output (errors always "
                         "print)")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on warnings too")
    ap.add_argument("--no-retrace", action="store_true",
                    help="network mode: skip the abstract-inference "
                         "re-trace, rely on build-time markers (the "
                         "executor gate's fast mode)")
    ap.add_argument("--cost", action="store_true",
                    help="print the static cost-model table (per-op "
                         "FLOPs/bytes/params + totals) instead of "
                         "running the verifier")
    ap.add_argument("--memory", action="store_true",
                    help="print the static memory-planner table "
                         "(analysis/memory.py: peak bytes, high-water "
                         "op, top live tensors) instead of running "
                         "the verifier")
    ap.add_argument("--batch", type=int, default=1,
                    help="--cost/--memory: batch size bound to "
                         "dynamic (-1) dims (default 1)")
    ap.add_argument("--limit", type=int, default=20,
                    help="--cost/--memory: table rows to print "
                         "(heaviest first; default 20, --memory "
                         "default 10)")
    args = ap.parse_args(argv)

    if args.list_networks:
        for n in sorted(NETWORKS):
            print(n)
        return 0
    if bool(args.model_dir) == bool(args.network):
        ap.error("give exactly one of: a model dir, or --network NAME")

    if args.cost:
        cost = cost_report(network=args.network,
                           model_dir=args.model_dir, batch=args.batch)
        print(cost.to_json(indent=2) if args.json
              else cost.table(limit=args.limit))
        return 0

    if args.memory:
        mem = memory_report(network=args.network,
                            model_dir=args.model_dir, batch=args.batch)
        limit = min(args.limit, 10) if args.limit == 20 else args.limit
        print(mem.to_json(indent=2) if args.json
              else mem.table(limit=limit))
        return 0

    if args.network:
        report = lint_network(args.network, retrace=not args.no_retrace)
    else:
        report = lint_model_dir(args.model_dir)

    if args.json:
        print(report.to_json())
    else:
        from paddle_tpu.analysis import Severity
        min_sev = Severity.ERROR if args.quiet else Severity.INFO
        print(report.render_text(min_severity=min_sev))
    if not report.ok:
        return 1
    if args.strict and report.warnings:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
