"""The trace reduction: on a hand-made trace whose answers are known by
construction, and on a small trace recorded on a TPU v5e
(fixtures/train_s2048_two_steps.json.gz: the operations line of device
0 over two steps of transformer-base.train-s2048, PR 24)."""
import os
import re

import pytest

from chipbench import trace as T

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "train_s2048_two_steps.json.gz")


def _plain(ops, marker=None, device=0):
    host = [{"name": "python3", "events": [[T.MARKER, marker, 10.0]]}] \
        if marker is not None else []
    return {"planes": [
        {"name": "/host:CPU", "lines": host},
        {"name": f"/device:TPU:{device}", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 0.0, 1e6]]},
            {"name": T.OPS_LINE, "events": ops}]}]}


KERNEL = "step_fn.24 custom-call:tpu_custom_call"
OPS = [["fusion.1 fusion", 100.0, 50.0],                  # 100-150
       ["all-reduce.3 all-reduce", 140.0, 30.0],          # 140-170
       [KERNEL, 200.0, 100.0],                            # 200-300
       ["fusion.1 fusion", 320.0, 30.0],                  # 320-350
       ["all-gather-start.2 all-gather-start", 400.0, 20.0],
       ["custom-call.9 custom-call:ConcatBitcast", 420.0, 1.0]]


def test_short_name_of_an_hlo_line():
    kernel = ('%step_fn.24 = (bf16[8,8,2048,64]{3,2,1,0:T(8,128)(2,1)}, '
              'f32[8,8,2048,128]{3,2,1,0:T(8,128)}) custom-call(bf16[8,8,'
              '2048,64]{3,2,1,0:T(8,128)(2,1)S(1)} %copy_bitcast_fusion'
              '.71, f32[8,1,2048,2048]{3,2,1,0:T(8,128)} %custom-call.3)'
              ', custom_call_target="tpu_custom_call", operand_layout_'
              'constraints={bf16[8,8,2048,64]{3,2,1,0}}')
    assert T.short_name(kernel) == KERNEL
    assert T.CUSTOM_CALL.search(T.short_name(kernel))
    fusion = ('%divide_subtract_fusion = (f32[512,32000]{1,0:T(8,128)}, '
              'f32[512,32000]{1,0:T(8,128)}) fusion(f32[512,32000]{1,0:'
              'T(8,128)} %p, bf16[16384,512]{1,0} %custom-call.391), '
              'kind=kOutput, calls=%fused_computation.158')
    # an operand called custom-call does not make a fusion a kernel
    assert T.short_name(fusion) == "divide_subtract_fusion fusion"
    assert not T.CUSTOM_CALL.search(T.short_name(fusion))
    glue = '%custom-call.23 = f32[32000,512]{1,0} custom-call(f32[8000,' \
           '512]{1,0} %slice-done.28), custom_call_target="ConcatBitcast"'
    assert T.short_name(glue) == "custom-call.23 custom-call:ConcatBitcast"
    assert not T.CUSTOM_CALL.search(T.short_name(glue))
    ar = '%all-reduce-start.3 = f32[512]{0} all-reduce-start(f32[512]{0}' \
         ' %x), channel_id=4, replica_groups={{0,1},{2,3}}'
    assert T.COLLECTIVE.search(T.short_name(ar))
    assert T.short_name("chipbench::window_start") == \
        "chipbench::window_start"


def test_busy_is_the_union_and_idle_is_its_complement():
    ops = T.device_ops(_plain(OPS), 0)
    assert T.busy_ns(ops) == 70 + 100 + 30 + 21
    gaps = T.idle_gaps(ops, 0.0, 500.0)
    assert gaps == [(0.0, 100.0), (170.0, 200.0), (300.0, 320.0),
                    (350.0, 400.0), (421.0, 500.0)]
    assert T.busy_ns(ops) + sum(b - a for a, b in gaps) == 500.0
    # "XLA Modules" spans the whole program and is not an operation
    assert all(n != "jit_step" for n, _s, _d in ops)


def test_clipping_to_the_window():
    ops = T.device_ops(_plain(OPS), 0, t0=120.0, t1=250.0)
    assert [(n, s, d) for n, s, d in ops] == [
        ("fusion.1 fusion", 120.0, 30.0),
        ("all-reduce.3 all-reduce", 140.0, 30.0), (KERNEL, 200.0, 50.0)]


def test_kernel_and_collective_sums_and_top_ops():
    ops = T.device_ops(_plain(OPS), 0)
    assert T.total_ns(ops, T.CUSTOM_CALL) == 100.0
    assert T.total_ns(ops, T.COLLECTIVE) == 50.0
    top = T.top_ops(ops, k=3)
    assert top[0][0].startswith("tpu_custom_call")
    assert top[1][0] == "fusion.1 fusion"
    assert top[1][1] == pytest.approx(80e-9)
    assert top[2][0] == "all-reduce (all)"
    assert top[2][1] == pytest.approx(30e-9)


def test_gaps_go_to_the_innermost_host_span():
    gaps = [(0.0, 100.0), (170.0, 200.0), (350.0, 400.0)]
    spans = [("trace::step", 0.0, 500.0),           # covers everything
             ("pipeline::fetch_sync", 10.0, 95.0),  # inside gap 1
             ("pipeline::dispatch", 172.0, 178.0)]  # a fifth of gap 2
    got = dict(T.attribute_gaps(gaps, spans))
    assert got["pipeline::fetch_sync"] == pytest.approx(85e-9)
    # gap 2: no inner span covers half of it, the step span has it all
    assert got["trace::step"] == pytest.approx((30 + 50) * 1e-9)
    assert got["host:untracked"] == pytest.approx(15e-9)
    assert "pipeline::dispatch" not in got
    assert dict(T.attribute_gaps(gaps, []))["host:untracked"] == \
        pytest.approx(180e-9)


def test_reduced_window_from_the_marker_and_two_devices():
    plain = _plain(OPS, marker=1000.0)
    plain["planes"].append(
        {"name": "/device:TPU:1", "lines": [
            {"name": T.OPS_LINE,
             "events": [["fusion.1 fusion", 0.0, 250.0]]}]})
    assert T.marker_ns(plain) == 1000.0
    assert T.device_ids(plain) == [0, 1]
    red = T.Reduced(plain, chips=2, window_ns=(0.0, 500.0), host_spans=[
        ("generation::prefill[12]", 300.0, 320.0)])
    assert red.window_s == pytest.approx(500e-9)
    assert red.busy_s == pytest.approx((221 + 250) / 2 * 1e-9)
    assert red.busy_on(0) == pytest.approx(221e-9)
    assert red.seconds(T.COLLECTIVE, 0) == pytest.approx(50e-9)
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert dict(b["idle_gaps"])["generation::prefill"] == \
        pytest.approx(20e-9)
    with pytest.raises(ValueError):
        T.Reduced({"planes": [{"name": "/host:CPU", "lines": []}]}, 1)


def test_recorded_tpu_trace():
    """Two steps recorded on the chip: the numbers a reader can check
    against the file by hand (PERF.md, PR 24 has the full window)."""
    plain = T.load_plain(FIXTURE)
    assert T.device_ids(plain) == [0]
    ops = T.device_ops(plain, 0)
    t0, t1 = ops[0][1], max(s + d for _n, s, d in ops)
    busy, wall = T.busy_ns(ops), t1 - t0
    gaps = T.idle_gaps(ops, t0, t1)
    assert busy + sum(b - a for a, b in gaps) == pytest.approx(wall)
    assert 0.5 < busy / wall <= 1.0
    kernel = T.total_ns(ops, T.CUSTOM_CALL)
    calls = [n for n, _s, _d in ops if T.CUSTOM_CALL.search(n)]
    # 72 flash custom calls a step (PERF.md, PR 22), two steps
    assert len(calls) == 2 * 72
    assert 0.2 < kernel / busy < 0.9
    assert T.total_ns(ops, T.COLLECTIVE) == 0.0     # one chip
    assert not any(re.match(r"^jit_", n) for n, _s, _d in ops)
    top = T.top_ops(ops)
    assert top[0][0].startswith("tpu_custom_call")
    assert top[0][1] == pytest.approx(kernel * 1e-9)
