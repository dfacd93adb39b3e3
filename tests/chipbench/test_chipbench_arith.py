"""chipbench/arith.py against the program's static cost model and
XLA's cost_analysis on a small naive-path train step.

Stated discrepancy: arith counts a causal self-attention as half the
score matrix and leaves out softmax, layer norm, bias and optimizer
FLOPs; the other two count the dense masked attention the naive path
executes, and the elementwise work. At these toy widths that puts arith
2-6 % below both; at transformer-base widths the elementwise share
shrinks further."""
import numpy as np
import pytest

from chipbench import arith, device


@pytest.mark.parametrize("b,s,n_layer,h,d,di,v", [
    (2, 64, 2, 4, 128, 256, 512), (4, 128, 1, 4, 256, 1024, 4096)])
def test_train_flops_agree_with_cost_model_and_xla(b, s, n_layer, h, d,
                                                   di, v):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel.collective_audit import aot_compiled_for
    main, startup, f = transformer.build_train(
        src_vocab=v, trg_vocab=v, max_len=s, n_layer=n_layer, n_head=h,
        d_model=d, d_inner=di)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {k: rng.randint(1, v, (b, s, 1)).astype(np.int64)
            for k in ("src_ids", "trg_ids", "trg_labels")}
    feed["pos_ids"] = np.arange(s, dtype=np.int64)
    exe.run(main, feed=feed, fetch_list=[f["loss"]])
    ours = arith.encdec_train_flops(b, s, n_layer=n_layer, n_head=h,
                                    d_model=d, d_inner=di, trg_vocab=v)
    model = exe.last_cost.flops
    xla = aot_compiled_for(exe, main).cost_analysis()
    xla = (xla[0] if isinstance(xla, list) else xla)["flops"]
    exe.close()
    assert 0.90 < ours / model <= 1.0, (ours, model)
    assert 0.90 < ours / xla <= 1.0, (ours, xla)


def test_published_shapes_give_the_planned_flops_per_token():
    base = dict(n_layer=6, n_head=8, d_model=512, d_inner=2048,
                trg_vocab=32000)
    assert round(arith.encdec_train_flops(8, 2048, **base)
                 / (8 * 2048) / 1e6) == 551
    assert round(arith.encdec_train_flops(64, 256, **base)
                 / (64 * 256) / 1e6) == 386
    parts = arith.encdec_forward_flops(8, 2048, **base)
    assert 0.35 < parts["attention"] / (
        parts["attention"] + parts["matmul"]) < 0.45


def test_flash_is_compute_bound_at_2048_and_bandwidth_bound_when_short():
    peaks = device.peaks_for("TPU v5 lite")
    long = arith.flash_call_cost(8, 8, 2048, 2048, 64, False, False)
    short = arith.flash_call_cost(8, 8, 64, 64, 64, False, False)
    assert arith.roofline_seconds(long["flops"], long["bytes"],
                                  peaks)["bound"] == "compute"
    assert arith.roofline_seconds(short["flops"], short["bytes"],
                                  peaks)["bound"] == "bandwidth"
    bwd = arith.flash_call_cost(8, 8, 2048, 2048, 64, False, True)
    assert bwd["flops"] == 2.5 * long["flops"]
    causal = arith.flash_call_cost(8, 8, 2048, 2048, 64, True, False)
    assert causal["flops"] == 0.5 * long["flops"]
    step = arith.encdec_flash_cost(8, 2048, 6, 8, 512)
    assert step["flops"] == 6 * 2.5 * 3.5 * long["flops"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(device.DeviceError):
        device.peaks_for("TPU v9 imaginary")
    assert device.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
