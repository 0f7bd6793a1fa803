"""The trace reduction, on a hand-made trace with known answers and on a
small trace recorded on the chip (``data/trace_small.json.gz``: 60 ms of
``qwen05b.chat`` on one TPU v5e, two decode steps and a prefill, op names
cut to their instruction names), whose answers are recomputed here by
brute force."""
import os

import numpy as np
import pytest

from harness import trace as T
from harness.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json.gz")


def hand_made():
    # window 0..100 ns from the harness spans; ops cover 10-30, 25-40
    # (overlapping), 60-70; the while loop encloses 10-40
    spans = [("submit", 0, 5), ("engine_tick", 5, 80), ("record", 85, 10),
             ("wait_due", 95, 5)]
    ops = [("%while.3 = (...) while(...)", 10, 30),
           ("%itq3_matvec_pallas.1 = f32[8] custom-call()", 10, 20),
           ("%attn_q8_pallas.2 = f32[8] custom-call()", 25, 15),
           ("%fusion.7 = f32[8] fusion()", 60, 10)]
    progs = [("jit__decode_impl(123)", 10, 60)]
    return Trace(ops, progs, spans)


def test_busy_and_idle():
    tr = hand_made()
    assert tr.window() == (0, 100)
    assert T.busy_intervals(tr.ops, 0, 100) == [[10, 40], [60, 70]]
    assert T.busy_ns(tr.ops, 0, 100) == 40
    assert T.idle_share(tr) == pytest.approx(0.6)
    assert T.busy_ns(tr.ops, 35, 65) == 10  # clipped to the window


def test_kernel_time_and_labels():
    tr = hand_made()
    assert T.time_ns(tr.ops, r"itq3_matvec") == 20
    assert T.time_ns(tr.ops, r"attn_q8") == 15
    assert T.time_ns(tr.programs, r"_decode_impl") == 60
    assert T.op_label(tr.ops[1][0]) == "itq3_matvec_pallas"
    assert [t[0] for t in T.top_ops(tr)] == [
        "itq3_matvec_pallas", "attn_q8_pallas", "fusion"]


def test_gap_attribution():
    tr = hand_made()
    # idle: 0-10 (submit 5 ns, tick 5 ns: the first span wins the tie),
    # 40-60 (tick), 70-100 (tick 15 ns, record 10, wait_due 5)
    assert T.idle_gaps(tr) == [("engine_tick", 30.0), ("engine_tick", 20.0),
                               ("submit", 10.0)]
    by = dict((k, v * 1e9) for k, v in T.idle_by_span(tr))
    assert by == {"engine_tick": pytest.approx(50.0),
                  "submit": pytest.approx(10.0)}


def test_roofline_share_from_a_trace():
    """The matvec reader: least time from shapes over kernel time."""
    import types
    from harness import costs
    from harness.model import Sizes
    read = _reader("itq3_matvec_roofline")
    s = Sizes(layers=1, d=256, heads=4, kv_heads=4, head_dim=64, d_ff=256,
              vocab=512, qkv_bias=False, rope_theta=1e4, norm_eps=1e-5)
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    need = sum(max(f / 1e12, b / 1e9) for f, b in
               (costs.itq3_matmul(8, k, n) for k, n in
                costs.step_projections(s)))
    ops = [("%itq3_matvec_pallas.1 = x", 10, int(need * 2e9)),
           ("%itq3_matvec_pallas.2 = x", 10**12, 5)]  # outside decode
    progs = [("jit__decode_impl(1)", 0, 10**11)]
    run = types.SimpleNamespace(
        trace=Trace(ops, progs, [("engine_tick", 0, 1)]), ticks=(0, 1),
        sizes=s, cell={"engine": {"slots": 8}}, peaks=peaks)
    assert read(run) == pytest.approx(50.0, rel=1e-6)


def _reader(name):
    from harness.spec import Registry
    return Registry().reader(name)


def test_recorded_trace_brute_force():
    tr = Trace.load(DATA)
    lo, hi = tr.window()
    assert tr.ops and tr.programs and hi > lo
    # busy time by painting every op onto a 100 ns grid
    step = 100
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for _, s, d in tr.ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // step):int(np.ceil((b - lo) / step))] = True
    brute = grid.sum() * step
    busy = T.busy_ns(tr.ops, lo, hi)
    assert abs(busy - brute) <= 2 * step * (len(tr.ops) + 1)
    share = T.idle_share(tr)
    assert 0.0 <= share < 1.0
    gaps = T.idle_gaps(tr)
    assert sum(g for _, g in gaps) == pytest.approx((hi - lo) - busy)
    assert {k for k, _ in gaps} <= set(T.HOST_SPANS) | {"none"}
    # qwen05b.chat on one v5e: 64 slots decode through the tiled kernel
    assert T.time_ns(tr.ops, r"itq3_matmul_pallas") > 0
    assert T.time_ns(tr.ops, r"attn_q8_pallas") > 0
    assert len(T.matching(tr.programs, r"_decode_impl")) == 2
    assert len(T.matching(tr.programs, r"_prefill_impl")) == 1
