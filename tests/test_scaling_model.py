"""Analytic scaling model (parallel/scaling_model.py): compile-only bench-shape audits feed a stated ICI ring
model. The full 8/16/64 x 4-config table lives in SCALING.json (built
by scaling_model.main in a 64-device process); this test executes the
machinery end-to-end at the 8-device size the conftest provides."""
import numpy as np
import pytest

from paddle_tpu.parallel import collective_audit as ca
from paddle_tpu.parallel import scaling_model as sm


def test_collective_time_model_formulas():
    # ring all-reduce of 100MB over 4 chips at 45GB/s: 2*B*(3/4)/bw
    t = sm._collective_time("all-reduce", 100e6, 1, 4)
    assert abs(t - (2 * 100e6 * 0.75 / sm.ICI_BW + 6e-6)) < 1e-9
    assert sm._collective_time("all-reduce", 100e6, 1, 1) == 0.0
    # permute: one hop
    t = sm._collective_time("collective-permute", 9e7, 2, 8)
    assert abs(t - (9e7 / sm.ICI_BW + 2e-6)) < 1e-9


def test_predict_combines_axes_and_reports_efficiency():
    inv = {("all-reduce", ("data",)): (10, int(1e8)),
           ("collective-permute", ("local",)): (3, 999),
           ("all-gather", ("data", "model")): (2, int(1e7))}
    out = sm.predict(inv, {"data": 8, "model": 2}, t_comp=0.05)
    assert 0 < out["eff_serial"] < 1
    assert out["per_axis_ms"]["data"] > out["per_axis_ms"]["model"]
    # local rows cost nothing
    inv2 = {("collective-permute", ("local",)): (3, 999)}
    assert sm.predict(inv2, {"data": 8}, 0.05)["eff_serial"] == 1.0


@pytest.mark.slow
def test_deepfm_audit_and_prediction_at_8_devices():
    """End-to-end: AOT bench-shape compile, ?-free inventory, sparse
    table-size invariance, and a sane efficiency prediction."""
    import jax
    hlo, mesh, ax = sm._config_deepfm(8, jax.devices())
    inv = ca.inventory(hlo, mesh)
    assert not any("?" in axes for (_k, axes) in inv)
    ca.assert_collectives(inv, [
        (("all-reduce", "reduce-scatter"), "data"),
        (("all-reduce",), "model"),
    ])
    pred = sm.predict(inv, ax, sm._t_comp("deepfm", ax))
    assert 0.5 < pred["eff_serial"] <= 1.0, pred
    # no batch-global gather over data (the round-4 sharded_lookup fix)
    gathers = [(k, a) for (k, a), _ in inv.items()
               if k == "all-gather" and "data" in a]
    assert not gathers, gathers

    # table-size invariance at the test-affordable size
    b1 = ca.axis_bytes(inv)["model"]
    hlo4, mesh4, _ = sm._config_deepfm(8, jax.devices(),
                                       num_features=int(4e5))
    b4 = ca.axis_bytes(ca.inventory(hlo4, mesh4))["model"]
    assert b1 == b4, (b1, b4)


def test_predict_multihost_decomposition():
    """Hierarchical all-reduce math: ICI bytes equal the flat ring's;
    DCN tier moves 2*(B/g)*(H-1)/H per chip at DCN constants; pure
    intra-host axes are untouched."""
    from paddle_tpu.parallel import scaling_model as sm

    B = 512 * 1024 * 1024
    inv = {("all-reduce", ("data",)): (1, B),
           ("all-gather", ("model",)): (2, B // 16)}
    axis = {"data": 16, "model": 4}
    t_comp = 0.050
    flat = sm.predict(inv, axis, t_comp)
    mh = sm.predict_multihost(inv, axis, t_comp, hosts=2)
    assert mh["hosts"] == 2 and mh["chips_per_host"] == 32
    # DCN component: 2*(B/g)*(H-1)/H / DCN_BW (+2*(H-1) hops), where
    # g = n/hosts is the intra-host group of the data-axis collective
    n = 16
    g = n // 2
    t_dcn_expect = (2 * (B // g) * (2 - 1) / 2 / sm.DCN_BW
                    + 1 * 2 * (2 - 1) * sm.DCN_LAT)
    assert abs(mh["t_dcn_ms"] - t_dcn_expect * 1e3) < 1e-3, (
        mh["t_dcn_ms"], t_dcn_expect * 1e3)
    # multi-host comm >= flat-ICI comm (DCN is slower), and the
    # model-axis (intra-host) share is identical in both
    assert mh["t_comm_ms"] >= flat["t_comm_ms"]
    assert mh["per_axis_ms"]["model"] == flat["per_axis_ms"]["model"]


def test_sensitivity_band_orders_with_bandwidth():
    """+-2x ICI bandwidth must move efficiency monotonically: half the
    bandwidth can only hurt, double can only help — and the report
    carries the band."""
    from paddle_tpu.parallel.scaling_model import ICI_BW, predict
    inv = {("all-reduce", ("data",)): (4, 40_000_000)}
    sizes = {"data": 8}
    base = predict(inv, sizes, t_comp=5e-3)
    lo = predict(inv, sizes, t_comp=5e-3, bw=ICI_BW * 0.5)
    hi = predict(inv, sizes, t_comp=5e-3, bw=ICI_BW * 2.0)
    assert lo["eff_serial"] < base["eff_serial"] < hi["eff_serial"]
    assert lo["t_comm_ms"] > base["t_comm_ms"] > hi["t_comm_ms"]


def test_a_tensor_parallel_layer_pair_is_modelled_at_eleven_tensors():
    """The model is driven by the audit, so it follows the layout's
    inventory: since ISSUE 50 the Megatron-paired transformer step
    brings 11 activation-sized tensors across 'model' an encoder +
    decoder layer pair (16 before: q, k, v each brought its input
    gradient across), and `predict` charges the axis those bytes."""
    from test_fanout_mul import BATCH, SEQ, WIDTHS, _compiled_step

    hlo, mesh = _compiled_step(1)
    inv = ca.inventory(hlo, mesh)
    a_tensor = BATCH // 2 * SEQ * WIDTHS["d_model"] * 4     # f32: no AMP
    _count, nbytes = inv[("all-reduce", ("model",))]
    assert nbytes == 11 * a_tensor
    pred = sm.predict(inv, {"data": 2, "model": 2}, t_comp=1e-3)
    want = sum(sm._collective_time(kind, b, cnt, 2)
               for (kind, axes), (cnt, b) in inv.items()
               if axes == ("model",))
    assert want > 11 * a_tensor / sm.ICI_BW     # a two-chip ring: B each
    assert abs(pred["per_axis_ms"]["model"] - want * 1e3) < 1e-3
