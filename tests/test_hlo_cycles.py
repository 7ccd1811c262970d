"""tools/hlo_cycles.py on a small scheduled-HLO fixture in the TPU backend's
print: ``estimated_cycles`` by op scope, a ``while`` body counted by the trip
count its condition states, the largest instructions with their bounds."""
import json

import pytest

from tools import hlo_cycles

_CFG = ('backend_config={"flag_configs":[],"window_config":{'
        '"estimated_cycles":"%d","iteration_bounds":[%s]}}')


def _cfg(cycles, *bounds):
    return _CFG % (cycles, ",".join(f'"{b}"' for b in bounds))


HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32]) -> f32[64] {{
  %p0 = bf16[64,32]{{1,0:T(8,128)(2,1)}} parameter(0)
  %c = f32[64,32]{{1,0}} convert(%p0)
  %e = f32[64,32]{{1,0}} exponential(%c), {_cfg(999, 9)}
  ROOT %r = f32[64]{{0}} reduce(%e), dimensions={{1}}
}}

%cond.1 (arg: (s32[], bf16[64,32])) -> pred[] {{
  %constant.9 = s32[]{{:T(128)}} constant(4), backend_config={{"flag_configs":[]}}
  %arg = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) parameter(0)
  %gte.0 = s32[]{{:T(128)}} get-tuple-element(%arg), index=0
  ROOT %lt.1 = pred[]{{:T(512)}} compare(%gte.0, %constant.9), direction=LT, metadata={{op_name="jit(step)/softmax_with_cross_entropy_grad#7/while/cond/lt"}}
}}

%body.1 (arg.1: (s32[], bf16[64,32])) -> (s32[], bf16[64,32]) {{
  %arg.1 = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) parameter(0)
  %gte.1 = s32[]{{:T(128)}} get-tuple-element(%arg.1), index=0
  %gte.2 = bf16[64,32]{{1,0:T(8,128)(2,1)}} get-tuple-element(%arg.1), index=1
  %fusion.7 = bf16[64,32]{{1,0:T(8,128)(2,1)}} fusion(%gte.2, %gte.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(step)/softmax_with_cross_entropy_grad#7/while/body/dynamic_update_slice"}}, {_cfg(100, 2, 1)}
  %bump = s32[]{{:T(128)}} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, {_cfg(1)}
  ROOT %tuple.1 = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) tuple(%bump, %fusion.7)
}}

ENTRY %main.1 (x: bf16[64,16], w: bf16[16,32]) -> bf16[64,32] {{
  %x = bf16[64,16]{{1,0:T(8,128)(2,1)}} parameter(0)
  %w = bf16[16,32]{{1,0:T(8,128)(2,1)}} parameter(1)
  %zero = s32[]{{:T(128)}} constant(0)
  %fusion.1 = bf16[64,32]{{1,0:T(8,128)(2,1)}} fusion(%x, %w), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="jit(step)/mul#3/dot_general" stack_frame_id=5}}, {_cfg(5000, 4, 2, 1)}
  %fusion.2 = f32[64]{{0:T(128)}} fusion(%fusion.1), kind=kInput, calls=%fused_computation.1, metadata={{op_name="jit(step)/softmax_with_cross_entropy#4/reduce_sum" stack_frame_id=6}}, {_cfg(700, 1, 8)}
  %kernel = bf16[64,32]{{1,0:T(8,128)(2,1)}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step)/fused_attention#5/pallas_call"}}
  %tuple.0 = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) tuple(%zero, %fusion.1)
  %while.1 = (s32[]{{:T(128)}}, bf16[64,32]{{1,0:T(8,128)(2,1)}}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={{op_name="jit(step)/softmax_with_cross_entropy_grad#7/while"}}, backend_config={{"flag_configs":[]}}
  %gte.9 = bf16[64,32]{{1,0:T(8,128)(2,1)}} get-tuple-element(%while.1), index=1
  %fusion.3 = bf16[16,32]{{1,0:T(8,128)(2,1)}} fusion(%x, %gte.9), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="jit(step)/mul_grad#8/transpose(jvp())/dot_general"}}, {_cfg(5200, 1, 2, 4)}
  ROOT %copy.1 = bf16[64,32]{{1,0:T(8,128)(2,1)}} copy(%gte.9), {_cfg(30)}
}}
"""


def test_cycles_by_op_type_count_a_loop_body_by_its_trips():
    got = hlo_cycles.summarize(HLO, by="type", top=3)
    # the loop's body: (100 + 1 of the counter, under the while's scope) x 4;
    # a fusion's own body (the 999) and the Mosaic kernel carry nothing
    assert got["by"] == {
        "mul_grad": 5200, "mul": 5000, "softmax_with_cross_entropy": 700,
        "softmax_with_cross_entropy_grad": 404, "(no scope)": 30}
    assert got["total"] == 11334
    assert list(got["by"]) == ["mul_grad", "mul", "softmax_with_cross_entropy",
                               "softmax_with_cross_entropy_grad",
                               "(no scope)"]
    assert [(it["name"], it["cycles"], it["bounds"], it["shape"])
            for it in got["top"]] == [
        ("fusion.3", 5200, "[1,2,4]", "bf16[16,32]"),
        ("fusion.1", 5000, "[4,2,1]", "bf16[64,32]"),
        ("fusion.2", 700, "[1,8]", "f32[64]")]


def test_cycles_by_scope_and_the_rendered_table():
    got = hlo_cycles.summarize(HLO, by="scope", top=4)
    assert got["by"]["softmax_with_cross_entropy_grad#7"] == 404
    assert got["by"]["mul_grad#8"] == 5200
    loop = got["top"][3]
    assert (loop["name"], loop["trips"], loop["cycles"], loop["computation"]
            ) == ("fusion.7", 4, 400, "body.1")
    text = hlo_cycles.render(got)
    assert "fusion.7 (softmax_with_cross_entropy_grad#7) x4 trips [2,1]" in text
    assert text.splitlines()[0].endswith("0.01 M")


def test_cli_prints_json_and_refuses_text_without_an_entry(tmp_path, capsys):
    path = tmp_path / "step.hlo.txt"
    path.write_text(HLO)
    assert hlo_cycles.main([str(path), "--json", "--top", "1"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["total"] == 11334 and len(out["top"]) == 1
    with pytest.raises(ValueError, match="ENTRY"):
        hlo_cycles.summarize("HloModule nothing\n")
