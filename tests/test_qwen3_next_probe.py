"""``tools/qwen3_next_probe.py`` at the cell's rehearsal sizes on the CPU:
the readings the harness cannot take run to their end and say what they are
for, through ``tools/laguna_probe.py``'s shared functions. The numbers of
PERF.md come from the chip."""
import json

import pytest

from tools import qwen3_next_probe as probe_tool


def probe(capsys, *argv):
    assert probe_tool.main([*argv, "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_controls_run_at_the_seeded_state_and_say_what_fails(capsys):
    got = probe(capsys, "controls", "--seed", str(2 ** 31 + 5))
    assert got["as_it_is"]["ok"] is True
    assert got["float8_weights"]["ok"] is False
    assert got["float8_weights"]["each"] > 3 * got["as_it_is"]["each"]
    # at the rehearsal's widths the decay, the step, the l2 norm and the
    # attention gate show; the shared gate and the budget need the published
    # widths' rows, the rotary embedding its scores
    for mechanism in ("decay", "beta", "l2_norm", "attention_gate"):
        assert got["no_" + mechanism]["ok"] is False, mechanism
        assert got["no_" + mechanism]["each"] > 5 * got["as_it_is"]["each"]
    for mechanism in ("shared_gate", "partial_rotary", "row_budget"):
        assert got["no_" + mechanism]["each"] > 2 * got["as_it_is"]["each"]
    # the reference with a bfloat16 state: what the check can tell of it
    assert got["no_bf16_state"]["each"] > 0


def test_without_takes_one_mechanism_out_and_keeps_the_parameters():
    from benchmark import run
    model = run.load_cell(probe_tool.CELL, rehearsal=True)["model"]
    assert probe_tool.without(model, "shared_gate")[
        "shared_expert_gate"] is False
    assert probe_tool.without(model, "partial_rotary")[
        "partial_rotary_factor"] == 1
    assert model["partial_rotary_factor"] == 0.25   # the cell's own untouched
    assert probe_tool.without(model, "row_budget")[
        "moe_row_budget"] == model["moe_row_budget"] // 10
    assert probe_tool.without(model, "decay") == model     # patched's part
    with pytest.raises(ValueError):
        probe_tool.without(model, "norm")
    from paddle_tpu import layers
    rule = layers.gated_delta_rule_packed
    with probe_tool.patched("decay"):
        assert layers.gated_delta_rule_packed is not rule
    assert layers.gated_delta_rule_packed is rule
