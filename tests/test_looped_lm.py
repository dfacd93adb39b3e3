"""models/looped_lm.py at the ``ouro-2p6b`` configuration's rehearsal
sizes against chipbench/reference_ouro.py (an independent f32
``jax.numpy`` forward: a Python loop over passes and layers, a masked
softmax over the whole score matrix, no scan, no kernel): the loss and
every parameter gradient, the shared arrays' one gradient against the
sum of the per-pass gradients of untied copies, the exit distribution
and the loss on a case small enough to do by hand, that the program
holds ONE loop whose body does not depend on the number of passes, one
pass against the straight-line build of the same blocks, what the loop
keeps of a pass for its transpose (the products' and the kernels'
outputs) against the body traced without ``jax.checkpoint``, and that
the other two decoder configurations' programs are the parent's."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench import reference, reference_ouro
from paddle_tpu import layers
from paddle_tpu.core.registry import grad_var_name
from paddle_tpu.models import looped_lm

S = 24
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench", "configs")
with open(os.path.join(CONFIGS, "ouro-2p6b.json")) as f:
    CONFIG = json.load(f)
# 2 layers run 4 times, 4 heads of 8 over 4, FFN 64, 96 ids
MODEL = dict(CONFIG["builder"]["args"],
             **CONFIG["rehearse"]["builder_args"])
MODEL["lr"] = 1e-3
KNOB = {"flash": "force", "composed": "0"}
T, L = MODEL["total_ut_steps"], MODEL["num_hidden_layers"]
STACK = L * reference_ouro.BLOCK_ARRAYS + 1      # the blocks, final norm


def _batch(seed, rows=3):
    rng = np.random.default_rng(seed)
    feed = {k: rng.integers(1, MODEL["trg_vocab"], (rows, S, 1),
                            dtype=np.int64)
            for k in ("src_ids", "trg_ids", "trg_labels")}
    feed["pos_ids"] = np.arange(S, dtype=np.int64)
    return feed


def _started(build=looped_lm.build_train, **kw):
    pt.reset_default_programs()
    pt.reset_global_scope()
    main, startup, fetch = build(max_len=S, **dict(MODEL, **kw))
    exe = pt.Executor()
    exe.run(startup)
    names = [p.name for p in main.all_parameters()]
    scope = pt.global_scope()
    # the gate's bias starts at 0 and the norm scales at 1: move them,
    # so that a gradient that ignores one cannot pass
    rng = np.random.default_rng(11)
    for n in names:
        w = np.array(scope.get(n))
        if w.ndim == 1:
            scope.set(n, (w + 0.2 * rng.standard_normal(w.shape))
                      .astype(np.float32))
    tape = [np.array(scope.get(n)) for n in names]
    return main, fetch, exe, names, tape


def _close(got, want, rtol, atol_rel, what):
    want = np.asarray(want)
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=rtol,
        atol=atol_rel * max(float(np.abs(want).max()), 1e-3), err_msg=what)


def _ids(batch):
    tok, lab = (np.asarray(batch[k]).reshape(batch[k].shape[0], -1)
                .astype(np.int32) for k in ("trg_ids", "trg_labels"))
    return tok, lab, np.arange(S, dtype=np.int32)


@pytest.mark.parametrize("path", ["composed", "flash"])
def test_loss_and_every_gradient_match_the_reference_in_f32(
        monkeypatch, path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    main, fetch, exe, names, tape = _started()
    assert all(p.trainable for p in main.all_parameters())
    assert len(names) == 1 + STACK + 1 + 2
    batch = _batch(0)
    loss, *grads = exe.run(
        main, feed=batch,
        fetch_list=[fetch["loss"]] + [grad_var_name(n) for n in names])
    exe.close()
    want = reference_ouro.loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=1e-5)
    want_grads = reference_ouro.grads(tape, batch, MODEL)
    assert len(want_grads) == len(names)
    for name, got, ref in zip(names, grads, want_grads):
        _close(got, ref, 2e-3, 1e-5, name)


def test_a_shared_array_has_one_gradient_the_sum_of_its_passes():
    """The reference with the passes' weights UNTIED (T copies of the
    stack) gives a gradient a pass; the program's one gradient array of
    a stack parameter is their sum, made inside the loop's transpose."""
    main, fetch, exe, names, tape = _started()
    ops = main.desc.global_block.ops
    written = [n for o in ops for n in o.output_names()]
    for n in names:      # ONE gradient variable a parameter, no @RENAME
        assert written.count(grad_var_name(n)) == 1, n
    stack_names = names[1:1 + STACK]
    (gop,) = [o for o in ops if o.type == "__vjp__"
              and o.attrs["fwd_op"]["type"] == "static_rnn"]
    assert sorted(gop.attrs["closure_names"]) == sorted(
        stack_names + ["pos_ids"])
    batch = _batch(3)
    got = exe.run(main, feed=batch,
                  fetch_list=[grad_var_name(n) for n in stack_names])
    exe.close()
    m = reference_ouro._Frozen(MODEL)
    table, stack, head, w_g, b_g = reference_ouro.split_tape(
        [jax.numpy.asarray(a) for a in tape], MODEL)
    tok, lab, pos = _ids(batch)
    with jax.default_matmul_precision("highest"):
        per_pass = jax.grad(
            lambda stacks: reference_ouro.exit_loss_sum(
                table, stacks, head, w_g, b_g, tok, lab, pos, m)
        )([list(stack) for _ in range(T)])
    assert len(per_pass) == T == 4
    for i, name in enumerate(stack_names):
        parts = [np.asarray(per_pass[t][i]) / tok.size for t in range(T)]
        _close(got[i], sum(parts), 2e-3, 1e-5, name)
        # and no single pass's gradient is the whole of it
        assert not np.allclose(parts[-1], sum(parts), rtol=0.05), name


def test_amp_step_stays_within_bf16_of_the_reference():
    """8 mantissa bits, toy widths, 72 tokens; the chip's cell is held
    to 5e-5 by the driver, the rehearsal to 5e-4."""
    main, fetch, exe, names, tape = _started()
    batch = _batch(2)
    with pt.amp.amp_guard():
        loss, = exe.run(main, feed=batch, fetch_list=[fetch["loss"]])
    after = [np.array(pt.global_scope().get(n)) for n in names]
    exe.close()
    want = reference_ouro.loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=2e-3)
    grads = reference_ouro.grads(tape, batch, MODEL)
    share = reference.descent_share(
        grads, [b - a for a, b in zip(tape, after)],
        reference.adam_first_step(grads, MODEL["lr"]))
    assert share["overall"] > 0.9
    assert min(s for s in share["per_array"] if s is not None) > 0.5


def test_the_exit_mass_tally_counts_steps_and_sums_to_them():
    main, fetch, exe, names, _tape = _started()
    for seed in range(3):
        exe.run(main, feed=_batch(seed), fetch_list=[fetch["loss"]])
    exe.close()
    scope = pt.global_scope()
    (name,) = [n for n in scope.local_names() if n.endswith(".exit_mass")]
    tally = np.asarray(scope.get(name))
    assert tally.shape == (T + 1,) and tally[-1] == 3.0
    assert tally[:T].sum() == pytest.approx(3.0, rel=1e-5)
    assert (tally[:T] > 0).all()
    assert name not in names            # no parameter, no optimizer
    # and on /metrics, as the share that left after each pass
    from paddle_tpu.observability import default_registry
    text = default_registry().render_prometheus()
    shares = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("paddle_tpu_exit_mass_share{")
              and name[:-len(".exit_mass")] in line]
    assert len(shares) == T and sum(shares) == pytest.approx(1.0, rel=1e-5)


def _exit_program(passes, tokens=2):
    pt.reset_default_programs()
    pt.reset_global_scope()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        lam = layers.data("lam", [passes, 1, tokens, 1],
                          append_batch_size=False)
        ce = layers.data("ce", [passes, 1, tokens, 1],
                         append_batch_size=False)
        exits = looped_lm.exit_distribution(lam, passes)
        loss = looped_lm.exit_weighted_loss(
            [layers.slice(ce, [0], [t], [t + 1]) for t in range(passes)],
            exits, 0.1)
    return main, startup, exits, loss


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_exit_distribution_sums_to_one(passes):
    main, startup, exits, _loss = _exit_program(passes, tokens=5)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.default_rng(passes)
    lam = rng.uniform(0.0, 1.0, (passes, 1, 5, 1)).astype(np.float32)
    lam[0, 0, 0, 0] = 1.0                  # a token that leaves at once
    ps = exe.run(main, feed={"lam": lam, "ce": np.zeros_like(lam)},
                 fetch_list=exits)
    exe.close()
    assert len(ps) == passes
    np.testing.assert_allclose(sum(np.asarray(p) for p in ps), 1.0,
                               rtol=1e-6)
    want = reference_ouro.exit_distribution(list(lam))
    for got, ref in zip(ps, want):
        np.testing.assert_allclose(np.asarray(got)[0], ref, rtol=1e-6)


def test_the_loss_of_a_two_token_case_done_by_hand():
    """T = 2: p = (lam, 1 - lam). Token a: lam .25, CE (2, 4); token b:
    lam 1 (it leaves at once: p = (1, 0), entropy 0), CE (3, 100)."""
    main, startup, _exits, loss = _exit_program(2)
    exe = pt.Executor()
    exe.run(startup)
    lam = np.float32([[.25, 1.0], [.5, .5]]).reshape(2, 1, 2, 1)
    ce = np.float32([[2.0, 3.0], [4.0, 100.0]]).reshape(2, 1, 2, 1)
    (got,) = exe.run(main, feed={"lam": lam, "ce": ce}, fetch_list=[loss])
    exe.close()
    entropy_a = -(.25 * np.log(.25) + .75 * np.log(.75))
    token_a = .25 * 2.0 + .75 * 4.0 - 0.1 * entropy_a
    token_b = 1.0 * 3.0 + 0.0 * 100.0 - 0.1 * 0.0
    assert float(got) == pytest.approx((token_a + token_b) / 2, rel=1e-6)


def _forward_program(passes):
    """The loss alone, no backward: (main, startup, loss)."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    cfg = looped_lm.model_cfg(dict(MODEL, total_ut_steps=passes))
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tokens = layers.data("trg_ids", [S, 1], dtype="int64")
        labels = layers.data("trg_labels", [S, 1], dtype="int64")
        pos = layers.data("pos_ids", [S], dtype="int64",
                          append_batch_size=False)
        loss = looped_lm.looped_lm(tokens, labels, pos, cfg)
    return main, startup, loss


def _scans(jaxpr, found=None):
    """Every scan equation of a jaxpr, the nested ones too (a Pallas
    kernel's own loops are not the program's)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _scans(inner, found)
    return found


def _step_jaxpr(main, fetch_name, rows=2, amp=False):
    exe = pt.Executor()
    scope = pt.global_scope()
    with pt.amp.amp_guard(amp):
        step = exe._compile(main.desc, main.desc.block(0), None,
                            [fetch_name], scope)
        feed = {k: v for k, v in _batch(0, rows).items()
                if main.desc.global_block.has_var(k)}
        state = [{n: scope.get(n) for n in names}
                 for names in (step.ro_names, step.rw_names)]
        jaxpr = jax.make_jaxpr(step.jitted)(feed, *state, np.int32(0))
    exe.close()
    return jaxpr.jaxpr


def test_one_loop_whose_body_does_not_depend_on_the_passes():
    sub_ops, body_eqns, outside = {}, {}, {}
    for passes in (1, 4):
        main, startup, loss = _forward_program(passes)
        exe = pt.Executor()
        exe.run(startup)
        exe.close()
        blocks = main.desc.blocks
        (op,) = [o for o in blocks[0].ops if o.type == "static_rnn"]
        assert len(blocks) == 2 and op.attrs["steps"] == passes
        sub_ops[passes] = [o.type for o in blocks[1].ops]
        outside[passes] = len(blocks[0].ops)
        (scan,) = _scans(_step_jaxpr(main, loss.name))
        assert scan.params["length"] == passes
        body_eqns[passes] = len(scan.params["jaxpr"].jaxpr.eqns)
    assert sub_ops[1] == sub_ops[4] and body_eqns[1] == body_eqns[4]
    # L attention sites in the ONE sub-block, whatever the passes
    assert sub_ops[4].count("scaled_dot_product_attention") == L
    assert sub_ops[4].count("rms_norm") == 4 * L + 1
    # outside the loop only the exit distribution's ops a pass grow
    assert 0 < outside[4] - outside[1] <= 12 * 3


def test_the_train_step_holds_scans_of_length_t_alone():
    counts = {}
    for passes in (1, 4):
        main, fetch, exe, _names, _tape = _started(total_ut_steps=passes)
        exe.close()
        scans = _scans(_step_jaxpr(main, fetch["loss"].name))
        assert scans and {s.params["length"] for s in scans} == {passes}
        counts[passes] = len(scans)
    assert counts[1] == counts[4]       # the forward scan, its transpose


def _unwrapped(monkeypatch):
    """The parent's loop: the body handed to the scan as it is."""
    monkeypatch.setattr(jax, "checkpoint", lambda body, **_kw: body)


def _stacked(main, fetch, rows=2):
    """[(dtype, shape a pass)] of what the AMP train step's forward scan
    stacks: its step outputs, and what it keeps for its transpose."""
    fwd, bwd = _scans(_step_jaxpr(main, fetch["loss"].name, rows,
                                  amp=True))
    assert not fwd.params["reverse"] and bwd.params["reverse"]
    return [(str(v.aval.dtype), tuple(v.aval.shape[1:]))
            for v in fwd.outvars[fwd.params["num_carry"]:]]


def _nbytes(arrays):
    return sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for dtype, shape in arrays)


@pytest.mark.parametrize("path", ["composed", "flash"])
def test_under_amp_the_loop_stacks_its_products_and_kernel_outputs(
        monkeypatch, path):
    """A pass leaves the transpose its carry, its products' outputs at
    the width the program holds them (bfloat16: never the float32
    accumulator), the output of each norm and rotary embedding that a
    product or the attention site reads, the flash forward's o and
    logsumexp, and nothing a norm or the rotary embedding holds inside,
    nothing of the post-norms, a cast or an activation."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    rows = 2
    main, fetch, exe, _names, _tape = _started()
    exe.close()
    kept = _stacked(main, fetch, rows)
    stream = (rows, S, MODEL["hidden_size"])
    ffn = (rows, S, MODEL["intermediate_size"])
    # the float32 arrays of the stream's shape are the loop's memory,
    # which keeps its init's dtype (the embedding's rows at pass 0), and
    # the pass's first norm, which reads it and hands on its width
    assert [a for a in kept if a[0] == "float32"
            and a[1] in (stream, ffn)] == 2 * [("float32", stream)]
    # a block's v, o and down products and the two norms a product
    # reads (less the pass's first), and the pass's step output
    assert kept.count(("bfloat16", stream)) == 5 * L - 1 + 1
    assert kept.count(("bfloat16", ffn)) == 2 * L       # gate, up
    # q and k as the attention site reads them (their products' outputs
    # are then not read by the transpose, and not stacked), the kernel's o
    heads = (rows, MODEL["num_attention_heads"], S, MODEL["head_dim"])
    assert kept.count(("bfloat16", heads)) == 2 * L + (
        L if path == "flash" else 0)
    assert len(kept) == 9 * L + 2 + (2 * L if path == "flash" else 0)
    with monkeypatch.context() as m:
        _unwrapped(m)
        before = _stacked(main, fetch, rows)
    assert len([a for a in before if a[0] == "float32"
                and a[1] in (stream, ffn)]) > 4 * L
    assert _nbytes(kept) <= 0.45 * _nbytes(before)


def test_the_transpose_runs_no_forward_kernel(monkeypatch):
    """The flash forward's outputs are kept, so the loop's transpose
    holds the backward kernel of each site and no forward kernel."""
    from test_vjp_reuse import _kernel_calls
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB["flash"])
    main, fetch, exe, _names, _tape = _started()
    exe.close()
    fwd, bwd = _scans(_step_jaxpr(main, fetch["loss"].name, amp=True))
    assert _kernel_calls(fwd.params["jaxpr"]) == {"flash_fwd": L}
    assert _kernel_calls(bwd.params["jaxpr"]) == {"flash_bwd_dkv_dq": L}


@pytest.mark.parametrize("path", ["composed", "flash"])
def test_loss_and_gradients_are_the_unwrapped_bodys_in_f32(
        monkeypatch, path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    batch = _batch(4)
    got = {}
    for wrapped in (True, False):
        with monkeypatch.context() as m:
            if not wrapped:
                _unwrapped(m)
            main, fetch, exe, names, _tape = _started()
            got[wrapped] = exe.run(
                main, feed=batch, fetch_list=[fetch["loss"]]
                + [grad_var_name(n) for n in names])
            exe.close()
    (loss, *grads), (want_loss, *want) = got[True], got[False]
    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    for name, g, w in zip(names, grads, want):
        _close(g, w, 1e-6, 1e-6, name)


def _straight_line(max_len, **kw):
    """The same blocks, norm, head, gate and loss with NO loop op: one
    pass, every op in the global block."""
    cfg = looped_lm.model_cfg(dict(kw, max_len=max_len))
    assert cfg["total_ut_steps"] == 1
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        layers.data("src_ids", [max_len, 1], dtype="int64")
        tokens = layers.data("trg_ids", [max_len, 1], dtype="int64")
        labels = layers.data("trg_labels", [max_len, 1], dtype="int64")
        pos = layers.data("pos_ids", [max_len], dtype="int64",
                          append_batch_size=False)
        helper = pt.layer_helper.LayerHelper("looped_lm")
        table = helper.create_parameter(
            pt.layer_helper.ParamAttr(
                initializer=pt.initializer.NormalInitializer(0.0, 1.0)),
            [cfg["trg_vocab"], cfg["hidden_size"]], "float32")
        h = looped_lm._embed(table, tokens)
        for i in range(cfg["num_hidden_layers"]):
            h = looped_lm.sandwich_block(h, pos, cfg, i)
        out = layers.unsqueeze(layers.rms_norm(h, cfg["rms_norm_eps"]),
                               [0])
        loss = looped_lm.exit_losses(out, labels, cfg)
        pt.optimizer.AdamOptimizer(learning_rate=cfg["lr"]).minimize(loss)
    return main, startup, {"loss": loss}


def test_one_pass_is_the_straight_line_build_to_f32_rounding():
    batch = _batch(5)
    results = {}
    for build in (looped_lm.build_train, _straight_line):
        main, fetch, exe, names, tape = _started(build, total_ut_steps=1)
        if results:     # the other build's weights, array by array
            other = results["tape"]
            assert [a.shape for a in tape] == [a.shape for a in other]
            for n, a in zip(names, other):
                pt.global_scope().set(n, a)
            assert not [o for o in main.desc.global_block.ops
                        if o.type == "static_rnn"]
        else:
            results["tape"] = tape
        loss, *grads = exe.run(
            main, feed=batch,
            fetch_list=[fetch["loss"]] + [grad_var_name(n) for n in names])
        exe.close()
        results[build] = (np.asarray(loss), [np.asarray(g) for g in grads])
    (la, ga), (lb, gb) = (results[b] for b in (looped_lm.build_train,
                                               _straight_line))
    np.testing.assert_allclose(la, lb, rtol=1e-6)
    for a, b in zip(ga, gb):
        _close(a, b, 1e-4, 1e-6, "gradient")


def test_misuse_raises():
    with pytest.raises(ValueError, match="no multiple"):
        looped_lm.build_train(num_attention_heads=6, num_key_value_heads=4)
    with pytest.raises(ValueError, match="at least 1"):
        looped_lm.build_train(total_ut_steps=0)


# the parent's (PR 42's) programs, serialised: this PR changes one line
# of decoder_moe.py (_out_scale without an init_depth) that neither
# configuration reaches. A PR that means to change these programs
# re-pins them and says so.
PARENT_PROGRAMS = {
    "laguna-xs2": (8192,
                   "9a3ecc42426c2b574aea63ff54f2f4e0ab2421e9d1070135bc7e0"
                   "66031684050",
                   "6d3013784c6f19650486768fbd1d7f3cd57cd852b0cb763a8dc83"
                   "4ea6208aa41"),
    "joyai-llm-flash": (4096,
                        "f7920a64282bbbe05e8e375f558498e2c747b4f38167fb8a"
                        "459c29234796ab18",
                        "825e179a6a5dba32c74126f2eb20438f79f3a922b13ca63d"
                        "2cb54da268a5b83e"),
}


@pytest.mark.parametrize("config", sorted(PARENT_PROGRAMS))
def test_the_other_decoder_programs_are_the_parents_bytes(config):
    from chipbench.drivers import resolve
    seq, main_hash, startup_hash = PARENT_PROGRAMS[config]
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        cfg = json.load(f)
    pt.reset_default_programs()
    pt.reset_global_scope()
    main, startup, _ = resolve(cfg["builder"]["function"])(
        **dict(cfg["builder"]["args"], max_len=seq))
    assert [hashlib.sha256(p.desc.to_json().encode()).hexdigest()
            for p in (main, startup)] == [main_hash, startup_hash]
