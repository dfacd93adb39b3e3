"""How masks reach attention (ISSUE 32): the decoder's self-attention
passes causality as the op's ``causal`` attr and the target's pads as a
key-row mask, never as a [Sq, Sk] array. The program that
models/transformer.py builds must still compute what the dense
triangle + pad bias computes (chipbench/reference.py, an independent f32
``jax.numpy`` enc-dec that keeps the dense triangle), through both SDPA
paths, and ``paddle_tpu_sdpa_sites_total`` says what each site was
handed."""
import collections

import numpy as np
import pytest

import paddle_tpu as pt
from chipbench import reference
from paddle_tpu import layers
from paddle_tpu.core.registry import grad_var_name
from paddle_tpu.models import transformer
from paddle_tpu.observability import default_registry

# PADDLE_TPU_PALLAS_SDPA for each path: the op leaves `use_flash` open,
# so the knob decides ("force": the kernels, in interpret mode here)
KNOB = {"flash": "force", "composed": "0"}
MODEL = dict(n_layer=2, n_head=2)
S, V = 16, 50


def _sdpa_sites():
    """{(path, mask, causal, window, group, layout): sites traced so
    far}."""
    fam = default_registry().get("paddle_tpu_sdpa_sites_total")
    if fam is None:
        return collections.Counter()
    return collections.Counter(
        {labels: child.value for labels, child in fam.samples()})


def _traced(before):
    return dict(_sdpa_sites() - before)


def _padded_batch(rng):
    """[3, S, 1] ids: a full row, a row with a few trailing pads, a row
    that is half pads; source and target pad at different lengths."""
    def ids(lengths):
        x = rng.randint(1, V, (len(lengths), S, 1)).astype(np.int64)
        for row, n in enumerate(lengths):
            x[row, n:] = 0
        return x
    trg = ids([S, S - 3, S // 2])
    lbl = np.concatenate([trg[:, 1:], np.zeros_like(trg[:, :1])], axis=1)
    return {"src_ids": ids([S - 5, S // 2, S]), "trg_ids": trg,
            "trg_labels": lbl, "pos_ids": np.arange(S, dtype=np.int64)}


def _build(**kw):
    pt.reset_default_programs()
    pt.reset_global_scope()
    return transformer.build_train(src_vocab=V, trg_vocab=V, max_len=S,
                                   d_model=32, d_inner=64, **MODEL, **kw)


@pytest.mark.parametrize("path", ["composed", "flash"])
def test_train_program_computes_the_dense_triangle_and_pad_bias(
        monkeypatch, path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    main, startup, fetch = _build()
    exe = pt.Executor()
    exe.run(startup)
    params = [p.name for p in main.all_parameters()]
    tape = [np.array(pt.global_scope().get(n)) for n in params]
    batch = _padded_batch(np.random.RandomState(0))
    before = _sdpa_sites()
    loss, *grads = exe.run(
        main, feed=batch,
        fetch_list=[fetch["loss"]] + [grad_var_name(n) for n in params])
    assert _traced(before) == {
        (path, "key_row", "0", "0", "1", "bshd"): 4,
        (path, "key_row", "1", "0", "1", "bshd"): 2}
    want = reference.encdec_loss(tape, batch, MODEL)
    np.testing.assert_allclose(float(np.asarray(loss).reshape(())), want,
                               rtol=2e-5)
    want_grads = reference.encdec_grads(tape, batch, MODEL)
    assert len(grads) == len(want_grads)
    for name, got, ref in zip(params, grads, want_grads):
        ref = np.asarray(ref)
        np.testing.assert_allclose(
            np.asarray(got), ref, rtol=2e-3,
            atol=2e-5 * max(float(np.abs(ref).max()), 1e-3), err_msg=name)


def _dense_mask_program():
    """One attention op handed a [b, 1, S, S] bias."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [2, S, 8], dtype="float32")
        mask = layers.data("mask", [1, S, S], dtype="float32")
        out = transformer._sdpa_op(q, q, q, mask, causal=False)
    return main, startup, out


@pytest.mark.parametrize("path", ["composed", "flash"])
def test_counter_tells_a_dense_mask_from_structure(monkeypatch, path):
    """The train program's own sites (3 x n_layer, none dense) are
    counted in the test above; an op handed a query axis counts
    `dense`, and building a program (shape inference) counts nothing."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_SDPA", KNOB[path])
    before = _sdpa_sites()
    main, startup, out = _dense_mask_program()
    assert _traced(before) == {}
    tri = np.triu(np.full((S, S), -1e9, np.float32), k=1)
    rng = np.random.RandomState(1)
    pt.Executor().run(main, fetch_list=[out], feed={
        "q": rng.randn(3, 2, S, 8).astype(np.float32),
        "mask": np.broadcast_to(tri, (3, 1, S, S)).copy()})
    assert _traced(before) == {(path, "dense", "0", "0", "1", "bhsd"): 1}
