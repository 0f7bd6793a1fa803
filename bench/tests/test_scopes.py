"""Model scopes and engine phases in a trace (``harness/scopes.py``): the
HLO attribution on a hand-written program with known answers, the gap
labels on a hand-made trace, and the readings on a trace recorded on the
chip (``data/trace_scopes.json.gz``: ``qwen05b.chat`` on one TPU v5e, four
decode steps around one admission, with the engine's spans and the scope
of every decode operation from the program's compiled HLO), recomputed
here by brute force."""
import gzip
import json
import os

import numpy as np
import pytest

from harness import scopes as S
from harness import trace as T
from harness.readings import DECODE_PROGRAM
from harness.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "trace_scopes.json.gz")

# a decode program in the compiled text's form: a loop over two layers
# whose carry holds a step counter, the activations, the KV pool and a
# stacked weight the loop passes through unchanged
HLO = """\
HloModule jit__decode_impl, is_scheduled=true

%fused_slice (param_0.1: f32[2,4,8], param_1: s32[]) -> f32[4,8] {
  %param_0.1 = f32[2,4,8]{2,1,0} parameter(0)
  %param_1 = s32[] parameter(1)
  %constant.9 = s32[] constant(0)
  %dynamic-slice.1 = f32[1,4,8]{2,1,0} dynamic-slice(%param_0.1, %param_1, %constant.9, %constant.9), dynamic_slice_sizes={1,4,8}, metadata={op_name="jit(_decode_impl)/while/body/dynamic_slice"}
  ROOT %bitcast.9 = f32[4,8]{1,0} bitcast(%dynamic-slice.1)
}

%fused_mlp (param_0.2: f32[4,8], param_1.2: f32[4,8]) -> f32[4,8] {
  %param_0.2 = f32[4,8]{1,0} parameter(0)
  %param_1.2 = f32[4,8]{1,0} parameter(1)
  ROOT %multiply.1 = f32[4,8]{1,0} multiply(%param_0.2, %param_1.2), metadata={op_name="jit(_decode_impl)/while/body/closed_call/mlp/mul"}
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(_decode_impl)/while/body/closed_call/attn/add"}
}

%body (p: (s32[], f32[4,8], s8[16,8], f32[2,4,8])) -> (s32[], f32[4,8], s8[16,8], f32[2,4,8]) {
  %p = (s32[], f32[4,8]{1,0}, /*index=2*/s8[16,8]{1,0}, f32[2,4,8]{2,1,0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%p), index=0
  %get-tuple-element.2 = f32[4,8]{1,0} get-tuple-element(%p), index=1
  %get-tuple-element.3 = s8[16,8]{1,0} get-tuple-element(%p), index=2
  %get-tuple-element.4 = f32[2,4,8]{2,1,0} get-tuple-element(%p), index=3
  %constant_dynamic-slice_fusion = f32[4,8]{1,0} fusion(%get-tuple-element.4, %get-tuple-element.1), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(_decode_impl)/while/body/dynamic_slice"}
  %fusion.1 = f32[4,8]{1,0} fusion(%constant_dynamic-slice_fusion, %get-tuple-element.2), kind=kLoop, calls=%fused_mlp
  %reduce.1 = f32[4]{0} reduce(%fusion.1, %constant.3), dimensions={1}, to_apply=%region_add, metadata={op_name="jit(_decode_impl)/while/body/closed_call/attn/reduce_sum"}
  %convert.1 = s8[1,8]{1,0} convert(%reduce.1), metadata={op_name="jit(_decode_impl)/while/body/closed_call/attn/kv_cache/convert;attn/kv_cache/reshape"}
  %dynamic-update-slice.1 = s8[16,8]{1,0} dynamic-update-slice(%get-tuple-element.3, %convert.1, %get-tuple-element.1, %constant.3), metadata={op_name="jit(_decode_impl)/while/body/closed_call/kv_cache/dynamic_update_slice"}
  %copy.5 = s8[16,8]{0,1} copy(%dynamic-update-slice.1)
  %constant.2 = s32[] constant(1)
  %add.1 = s32[] add(%get-tuple-element.1, %constant.2), metadata={op_name="jit(_decode_impl)/while/body/add"}
  ROOT %tuple.1 = (s32[], f32[4,8]{1,0}, s8[16,8]{0,1}, f32[2,4,8]{2,1,0}) tuple(%add.1, %fusion.1, %copy.5, %get-tuple-element.4)
}

%cond (p.1: (s32[], f32[4,8], s8[16,8], f32[2,4,8])) -> pred[] {
  %p.1 = (s32[], f32[4,8]{1,0}, s8[16,8]{1,0}, f32[2,4,8]{2,1,0}) parameter(0)
  %get-tuple-element.9 = s32[] get-tuple-element(%p.1), index=0
  %constant.5 = s32[] constant(2)
  ROOT %lt.1 = pred[] compare(%get-tuple-element.9, %constant.5), direction=LT, metadata={op_name="jit(_decode_impl)/while/cond/lt"}
}

ENTRY %main.1 (cache.1: s8[16,8], w.1: f32[2,4,8], x.1: f32[4,8]) -> (s8[16,8], f32[4,8]) {
  %cache.1 = s8[16,8]{1,0} parameter(0), metadata={op_name="cache['attn']['k']"}
  %w.1 = f32[2,4,8]{2,1,0} parameter(1), metadata={op_name="params['layers']['mlp']['up']"}
  %x.1 = f32[4,8]{1,0} parameter(2), metadata={op_name="tokens"}
  %copy.1 = s8[16,8]{0,1} copy(%cache.1)
  %constant.0 = s32[] constant(0)
  %tuple.0 = (s32[], f32[4,8]{1,0}, s8[16,8]{0,1}, f32[2,4,8]{2,1,0}) tuple(%constant.0, %x.1, %copy.1, %w.1)
  %while.1 = (s32[], f32[4,8]{1,0}, s8[16,8]{0,1}, f32[2,4,8]{2,1,0}) while(%tuple.0), condition=%cond, body=%body
  %get-tuple-element.20 = s8[16,8]{0,1} get-tuple-element(%while.1), index=2
  %copy.2 = s8[16,8]{1,0} copy(%get-tuple-element.20)
  %get-tuple-element.21 = f32[4,8]{1,0} get-tuple-element(%while.1), index=1
  ROOT %tuple.2 = (s8[16,8]{1,0}, f32[4,8]{1,0}) tuple(%copy.2, %get-tuple-element.21)
}
"""


def test_scope_of_names():
    assert S.scope_of("jit(_decode_impl)/while/body/closed_call/attn/"
                      "kv_cache/reshape") == "kv_cache"
    assert S.scope_of("jit(_decode_impl)/head/dot_general") == "head"
    # a merged name counts by its first part
    assert S.scope_of("a/attn/reshape;kv_cache/x") == "attn"
    assert S.scope_of("jit(_decode_impl)/while/body/add") is None
    assert S.scope_of(None) is None
    assert S.argument_scope("cache['attn']['k']") == "kv_cache"
    assert S.argument_scope("params['layers']['attn']['wq'].data") == "attn"
    assert S.argument_scope("params['layers']['ln2']['scale']") == "mlp"
    assert S.argument_scope("params['embed']") == "embed"
    assert S.argument_scope("positions") is None


def test_parse_hlo():
    comps, entry, roots = S.parse_hlo(HLO)
    assert entry == "main.1"
    assert set(comps) == {"fused_slice", "fused_mlp", "region_add", "body",
                          "cond", "main.1"}
    assert roots["body"] == "tuple.1"
    w = comps["main.1"]["while.1"]
    assert w.opcode == "while" and w.operands == ["tuple.0"]
    assert sorted(w.calls) == ["body", "cond"]
    g = comps["body"]["get-tuple-element.3"]
    assert (g.opcode, g.operands, g.index) == ("get-tuple-element", ["p"], 2)
    assert comps["main.1"]["cache.1"].op_name == "cache['attn']['k']"
    assert comps["body"]["copy.5"].op_name is None


def test_hlo_scopes_follow_copies_and_carries():
    sc = S.hlo_scopes(HLO)
    # the fusions' bodies and the reduction's combiner are not timed
    assert "multiply.1" not in sc and "add.9" not in sc
    want = {
        "fusion.1": "mlp",            # from its fused root's op_name
        "reduce.1": "attn",
        "convert.1": "kv_cache",      # innermost, first part of a merge
        "dynamic-update-slice.1": "kv_cache",
        "copy.5": "kv_cache",         # a copy XLA inserted: what it copies
        # the loop's slice of a weight it passes through: the weight's
        "constant_dynamic-slice_fusion": "mlp",
        "copy.1": "kv_cache",         # copies the pool argument
        "copy.2": "kv_cache",         # the carry, back to the body's write
        "add.1": None, "lt.1": None,  # loop control
    }
    assert {k: sc[k] for k in want} == want


def test_gap_labels_name_the_engine_phase():
    # window 0..100 from the harness spans; busy 10-40 and 60-70
    spans = [("submit", 0, 5), ("engine_tick", 5, 80), ("record", 85, 10),
             ("wait_due", 95, 5)]
    ops = [("%fusion.1 = f32[8] fusion()", 10, 30),
           ("%fusion.2 = f32[8] fusion()", 60, 10)]
    tr = Trace(ops, [("jit__decode_impl(1)", 10, 60)], spans)
    engine = [("serve.decode_prep", 5, 7),      # half of gap 0-10: not most
              ("serve.commit", 40, 18),         # 18 of gap 40-60
              ("serve.admit", 70, 18),          # 18 of gap 70-100
              ("serve.prefill_sync", 72, 15)]   # 15 of 30: not most
    assert S.labelled_gaps(tr, engine) == [
        ("engine_tick>serve.admit", 30.0), ("engine_tick>serve.commit", 20.0),
        ("submit", 10.0)]
    # with no engine span the labels are the harness's own
    assert S.labelled_gaps(tr, []) == T.idle_gaps(tr)
    assert dict(S.idle_by_phase(tr, engine)) == pytest.approx(
        {"engine_tick>serve.admit": 30e-9, "engine_tick>serve.commit": 20e-9,
         "submit": 10e-9})
    assert S.span_share(tr, engine, "serve.admit") == pytest.approx(0.18)
    assert S.span_share(tr, engine, "serve.decode_sync") is None


def test_old_recorded_trace_keeps_its_gaps():
    tr = Trace.load(os.path.join(DATA, "trace_small.json.gz"))
    assert S.labelled_gaps(tr, []) == T.idle_gaps(tr)


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        d = json.load(f)
    return Trace.load(RECORDED), d


def test_recorded_trace_scope_table(recorded):
    tr, d = recorded
    scopes, steps = d["decode_scopes"], d["decode_steps"]
    decodes = T.matching(tr.programs, DECODE_PROGRAM)
    assert len(decodes) == steps
    # brute force: each op inside a decode program, its scope by name
    want: dict = {}
    for _, s, dur in decodes:
        for name, start, d_op in tr.ops:
            label = T.op_label(name)
            if s <= start < s + dur and label not in T.ENCLOSING:
                key = scopes.get(S.instr_name(name), "?")
                want[key] = want.get(key, 0.0) + d_op
    got = S.scope_ns(tr, DECODE_PROGRAM, scopes)
    assert got == pytest.approx(want)
    assert "?" not in got  # every decode op is in the program's HLO
    total = sum(got.values())
    # the scopes cover the decode step; the pool's copies are kv_cache's
    assert got.get(None, 0.0) / total < 0.1
    kv = got["kv_cache"] / steps / 1e6
    assert kv > 0
    copies = sum(dur for name, start, dur in S.program_ops(tr, DECODE_PROGRAM)
                 if T.op_label(name) == "copy")
    copies_kv = sum(dur for name, start, dur in S.program_ops(
        tr, DECODE_PROGRAM) if T.op_label(name) == "copy"
        and scopes[S.instr_name(name)] == "kv_cache")
    assert copies_kv / copies > 0.9


def test_recorded_trace_phases(recorded):
    tr, d = recorded
    engine = [tuple(e) for e in d["engine_spans"]]
    assert {e[0] for e in engine} == set(S.ENGINE_SPANS)
    lo, hi = tr.window()
    # admission share by painting the admit spans onto a 1 us grid
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for name, s, dur in engine:
        if name == "serve.admit":
            a, b = max(s, lo), min(s + dur, hi)
            if b > a:
                grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = 1
    share = S.span_share(tr, engine, "serve.admit")
    assert share == pytest.approx(grid.sum() * 1000 / (hi - lo), abs=2e-3)
    gaps = S.labelled_gaps(tr, engine)
    base = T.idle_gaps(tr)
    # the same gaps, the same totals; labels only grow by engine phases
    assert sorted(g for _, g in gaps) == sorted(g for _, g in base)
    assert sorted(lbl.split(">")[0] for lbl, _ in gaps) == sorted(
        lbl for lbl, _ in base)
    assert any(lbl.startswith("engine_tick>serve.") for lbl, _ in gaps)


def test_report_runs_on_the_cpu(tiny_root, monkeypatch, capsys, tmp_path):
    """``trace_scopes.py`` drives the cell, both slices and the reduction;
    a CPU trace holds no TPU operation, so only the host side reads."""
    import jax

    import run as bench_run
    import trace_scopes
    from harness.spec import Registry
    devs = jax.devices()
    monkeypatch.setattr(bench_run, "device_info", lambda reg, chips: (
        {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}, devs))
    monkeypatch.setattr(bench_run, "check_paths", lambda stats: None)
    rc = trace_scopes.main(["--workload", "tiny.chat", "--seed", "4",
                            "--seconds", "2", "--out", str(tmp_path)],
                           reg=Registry(tiny_root))
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["traced"]["steps"] > 0 and out["untraced"]["steps"] > 0
    assert out["traced"]["decode_tick_ms"] > 0
    assert out["decode_steps"] == out["traced"]["steps"]
    assert set(out["phase_s"]) == set(S.ENGINE_SPANS)
    assert 0 < out["admit_share"] < 1
    assert (tmp_path / "tiny.chat.4.decode.hlo.gz").is_file()
