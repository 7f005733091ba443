"""`bench/scopes.py` on a program text in `compiled.as_text()`'s form, by
hand.  (The map on real compiles: `tests/test_tpu_compile.py`.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import layers  # noqa: E402
from bench import scopes as S  # noqa: E402

J = "jit(resnet34_apply)"
K = "jit(log_conv2d_fused_pallas)"
TEXT = f"""HloModule jit_resnet34_apply, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {{
  %param_0 = f32[4]{{0}} parameter(0)
  ROOT %maximum.0 = f32[4]{{0}} maximum(%param_0, %param_0), metadata={{op_name="{J}/stem/jit(relu)/max"}}
}}

ENTRY %main.9 (x.1: f32[1,8,8,3], w.1: s8[9,3,8]) -> f32[1,8] {{
  %x.1 = f32[1,8,8,3]{{3,2,1,0}} parameter(0), metadata={{op_name="x"}}
  %w.1 = s8[9,3,8]{{2,1,0}} parameter(1), metadata={{op_name="params['stem']['w']"}}
  %constant.2 = f32[] constant(0)
  %copy.4 = f32[1,8,8,3]{{2,1,3,0}} copy(%x.1), metadata={{op_name="x"}}
  %pad.5 = f32[1,10,10,3]{{3,2,1,0}} pad(%copy.4, %constant.2), padding=0_0x1_1x1_1x0_0, metadata={{op_name="{J}/stem/{K}/pad/jit(_pad)/pad" source_file="log_conv2d.py" source_line=497}}
  %copy-start.1 = (s8[9,3,8]{{2,1,0:S(1)}}, s8[9,3,8]{{2,1,0}}, u32[]) copy-start(%w.1)
  %copy-done.1 = s8[9,3,8]{{2,1,0:S(1)}} copy-done(%copy-start.1)
  %transpose.6 = s8[9,3,8]{{2,1,0}} transpose(%copy-done.1), dimensions={{0,1,2}}, metadata={{op_name="{J}/stem/{K}/weights/transpose"}}
  %log_conv2d_fused_pallas.7 = f32[1,8,8,8]{{3,2,1,0}} custom-call(%pad.5, %transpose.6), custom_call_target="tpu_custom_call", metadata={{op_name="{J}/stem/{K}/pallas_call"}}
  %fusion.8 = f32[1,8,8,8]{{3,2,1,0}} fusion(%log_conv2d_fused_pallas.7), kind=kLoop, calls=%fused_computation, metadata={{op_name="{J}/stem/jit(relu)/max"}}
  %reduce_window_max.9 = f32[1,4,4,8]{{3,2,1,0}} reduce-window(%fusion.8, %constant.2), window={{size=1x2x2x1}}, metadata={{op_name="{J}/pool/reduce_window_max"}}
  ROOT %dot.10 = f32[1,8]{{1,0}} dot(%reduce_window_max.9, %reduce_window_max.9), metadata={{op_name="{J}/head/dot_general"}}
}}
"""


def test_entry_instructions_and_paths():
    instrs = S.entry_instructions(TEXT)
    assert [n for n, _, _ in instrs][:3] == ["x.1", "w.1", "constant.2"]
    assert instrs[-1][0] == "dot.10"          # ROOT is an instruction too
    assert all("metadata" not in h for _, h, _ in instrs)
    hlo = dict((n, h) for n, h, _ in instrs)["pad.5"]
    assert hlo.startswith("%pad.5 = f32[1,10,10,3]")
    assert S.scope_path(f"{J}/stem/{K}/pad/jit(_pad)/pad") == ["stem", "pad"]
    assert S.scope_path("x") == []
    assert S.scope_path(f"{J}/stages.1.0.proj/{K}/pallas_call") == [
        "stages.1.0.proj"]
    merged = (f"{J}/pairs.0.dw/{K}/unscramble/reshape;unscramble/reshape;"
              "unscramble/transpose;unscramble/reshape")
    assert S.scope_path(merged) == ["pairs.0.dw", "unscramble"]


def test_scope_map():
    m = S.scope_map(TEXT)
    assert "x.1" not in m and "w.1" not in m          # parameters
    assert m["log_conv2d_fused_pallas.7"] == ("stem", S.KERNEL)
    assert m["pad.5"] == ("stem", "pad")
    assert m["transpose.6"] == ("stem", "weights")
    assert m["fusion.8"] == ("stem", S.GLUE)           # the ReLU
    assert m["reduce_window_max.9"] == ("pool", S.GLUE)
    assert m["dot.10"] == ("head", S.GLUE)
    # no scope: the readers' layer, and their role other than the kernel
    assert m["copy.4"] == ("stem", "pad")
    assert m["copy-done.1"] == ("stem", "weights")
    assert m["copy-start.1"] == ("stem", "weights")
    # read by two layers: none
    assert m["constant.2"] == (None, S.GLUE)


def _reduced():
    return {"busy_s": 10.0, "top_ops": [
        ["log_conv2d_fused_pallas.7", 5.0], ["copy.4", 1.5],
        ["pad.5", 1.0], ["fusion.8", 0.5], ["reduce_window_max.9", 0.75],
        ["dot.10", 0.25], ["transpose.6", 0.5], ["unknown.1", 0.5]]}


def test_reduce_and_conv_glue_by_hand():
    s = S.reduce(TEXT, _reduced())
    assert s["conv_layers"] == ["stem"]
    assert s["conv_glue_s"] == pytest.approx(1.5 + 1.0 + 0.5 + 0.5)
    assert s["named_s"] == pytest.approx(10.0 - 0.5)
    assert s["unmapped"] == [["unknown.1", 0.5]]
    by = {(lay, role): sec for lay, role, sec in s["by_layer_role"]}
    assert by[("stem", "pad")] == pytest.approx(2.5)
    assert by[("stem", S.KERNEL)] == 5.0 and by[(None, S.GLUE)] == 0.5
    assert S.conv_glue({"scopes": s}) == pytest.approx(100 * 3.5 / 10.0)
    assert "stem" in S.table(s) and "95.00%" in S.table(s)


def test_conv_glue_none_without_anything_to_read():
    assert S.conv_glue({}) is None
    no_conv = S.reduce(TEXT.replace('custom_call_target="tpu_custom_call"',
                                    'custom_call_target="other"'),
                       _reduced())
    assert no_conv["conv_layers"] == []
    assert S.conv_glue({"scopes": no_conv}) is None
    idle = dict(S.reduce(TEXT, _reduced()), busy_s=0.0)
    assert S.conv_glue({"scopes": idle}) is None


def test_layers_checks():
    lay = {"named_s": 9.6, "busy_s": 10.0}
    frame = {"h2d": 1, "launch": 1, "device": 1, "device_wait": 1,
             "return": 1, "frame": 5.05}
    lau = {"paired": 4, "programs": 4, "offset_bounds_s": [1e-3, 2e-3],
           "frames": [frame]}
    got = layers.checks(lay, lau, single_stream=True)
    assert got["ok"] and got["frame_sum"]["value"] == pytest.approx(
        1 - 5 / 5.05)
    assert not layers.checks(dict(lay, named_s=9.4), lau, True)["ok"]
    assert not layers.checks(lay, dict(lau, offset_bounds_s=[2e-3, 1e-3]),
                             True)["ok"]
    assert not layers.checks(lay, dict(lau, frames=[dict(frame, frame=5.2)]),
                             True)["ok"]
    assert not layers.checks(lay, dict(lau, paired=3), True)["ok"]
    assert layers.checks(lay, dict(lau, frames=[], offset_bounds_s=None),
                         single_stream=False)["ok"]
