"""``tools/kimi_linear_probe.py`` at the cell's rehearsal sizes on the CPU:
the readings the harness cannot take run to their end and say what they are
for, through ``tools/laguna_probe.py``'s shared functions. The numbers of
PERF.md come from the chip."""
import json
import math

import pytest

from tools import kimi_linear_probe as probe_tool


def probe(capsys, *argv):
    assert probe_tool.main([*argv, "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_controls_run_at_the_seeded_state_and_say_what_shows(capsys):
    got = probe(capsys, "controls", "--seed", str(2 ** 31 + 5))
    assert got["as_it_is"]["ok"] is True
    assert got["float8_weights"]["each"] > 2 * got["as_it_is"]["each"]
    # the rule's mechanisms show in the KDA layers' o sizes, the
    # channel-averaged decay (the scalar rule under this model's name) among
    # them; the gates in the block means; the routed scale and the budget in
    # the routed entries' own value
    for mechanism in ("decay", "channel_decay", "beta", "l2_norm"):
        control = got["no_" + mechanism]
        assert control["ok"] is False, mechanism
        assert max(control["parts"]["o_size_rel"]) > 0.1, mechanism
    for mechanism in ("sigmoid_gate", "out_gate"):
        control = got["no_" + mechanism]
        assert control["ok"] is False, mechanism
        assert control["parts"]["blocks"] > 5 * got["as_it_is"]["each"]
    assert all(0.5 < r < 0.65 for r in
               got["no_routed_scale"]["parts"]["held_norm_rel"])    # 1 - 1/2.446
    assert all(0.4 < r < 0.95 for r in
               got["no_row_budget"]["parts"]["held_norm_rel"])
    assert max(got["as_it_is"]["parts"]["held_norm_rel"]) < 0.05
    assert max(got["as_it_is"]["parts"]["o_size_rel"]) < 0.01
    # the rotation and the softmax scale need sharpened scores
    # (tests/test_decoder_kimi_linear.py), a bfloat16 state the op's tests
    for mechanism in ("nope", "softmax_scale", "bf16_state"):
        assert got["no_" + mechanism]["each"] > 0


def test_kernels_and_parts_run_to_their_end(capsys):
    got = probe(capsys, "parts", "--seed", str(2 ** 31 + 5))
    assert len(got["held_norms"]) == 2 and len(got["o_sizes"]) == 2
    assert 0 < max(got[k] for k in ("blocks", "held_norm", "o_size")) < 2.2e-3
    got = probe(capsys, "kernels", "--chunks", "16")
    assert got["composed_fwd_ms"] > 0 and got["composed_temp_gb"] > 0


def test_without_takes_one_mechanism_out_and_keeps_the_parameters():
    from benchmark import run
    from paddle_tpu import layers
    from paddle_tpu.ops import pallas_delta
    model = run.load_cell(probe_tool.CELL, rehearsal=True)["model"]
    assert probe_tool.without(model, "routed_scale")[
        "routed_scaling_factor"] == 1.0
    assert probe_tool.without(model, "softmax_scale")[
        "attention_multiplier"] == 1 / math.sqrt(model["qk_nope_head_dim"])
    assert probe_tool.without(model, "nope")["mla_use_nope"] is False
    assert model["routed_scaling_factor"] == 2.446 and model["mla_use_nope"]
    assert probe_tool.without(model, "row_budget")[
        "moe_row_budget"] == model["moe_row_budget"] // 8
    assert probe_tool.without(model, "channel_decay") == model  # patched's
    with pytest.raises(ValueError):
        probe_tool.without(model, "norm")
    rule, norm, unit = (layers.gated_delta_rule_packed, layers.rms_norm,
                        pallas_delta.unit)
    for mechanism, owner, name, was in (
            ("decay", layers, "gated_delta_rule_packed", rule),
            ("channel_decay", layers, "gated_delta_rule_packed", rule),
            ("beta", layers, "gated_delta_rule_packed", rule),
            ("sigmoid_gate", layers, "rms_norm", norm),
            ("out_gate", layers, "rms_norm", norm),
            ("l2_norm", pallas_delta, "unit", unit)):
        with probe_tool.patched(mechanism):
            assert getattr(owner, name) is not was
        assert getattr(owner, name) is was


def test_the_kernels_sweep_takes_heads_a_step_and_puts_the_constant_back(
        capsys):
    """``--step-heads`` / ``--scalar-reps`` parse; the rehearsal's heads of
    16 are not the kernels', so nothing is timed there. ``delta_kernels`` on
    the interpreter at heads of 128: a row each value in ``STEP_HEADS``'
    place with what ``step_heads`` took of three heads and the empty bodies'
    time beside it, the module as it was afterwards. ``scalar_kernels``
    likewise by value heads a key head: the key heads a step that fit the
    limit's value heads."""
    import numpy as np
    from paddle_tpu.ops import pallas_delta
    got = probe(capsys, "kernels", "--chunks", "16", "--step-heads", "1", "2",
                "--scalar-reps", "1", "2")
    assert got["delta"] == [] and got["scalar"] == []
    def now():
        return (pallas_delta.STEP_HEADS, pallas_delta._channel_forward,
                pallas_delta._channel_backward, pallas_delta._scalar_forward,
                pallas_delta._scalar_backward)
    taken = now()
    rng = np.random.RandomState(5)
    rows = probe_tool.delta_kernels(
        probe_tool.channel_feeds(1, 128, 3, 128, rng), [64], [], [2, 4], True)
    assert [(r["chunk"], r["step_heads"]) for r in rows] == [(64, 1), (64, 3)]
    assert all(r["finite"] and min(r["fwd_ms"], r["bwd_ms"], r["empty_fwd_ms"],
                                   r["empty_bwd_ms"]) > 0 for r in rows)
    assert taken == now()
    (row,) = probe_tool.delta_kernels(
        probe_tool.channel_feeds(1, 64, 1, 128, rng), [64], [], [], True)
    assert row["step_heads"] == 1 and "empty_fwd_ms" not in row
    rows = probe_tool.scalar_kernels(1, 64, 4, 128, 64, [2, 3], [], True, rng)
    assert [(r["key_heads"], r["rep"], r["step_heads"]) for r in rows] == [
        (2, 2, 2)] and "empty_fwd_ms" not in rows[0]
    rows = probe_tool.scalar_kernels(1, 64, 4, 128, 64, [2, 1], [2, 4], True,
                                     rng)
    assert [(r["rep"], r["step_heads"]) for r in rows] == [
        (2, 1), (2, 2), (1, 2), (1, 4)]
    assert all(min(r["fwd_ms"], r["bwd_ms"], r["empty_fwd_ms"],
                   r["empty_bwd_ms"]) > 0 for r in rows)
    assert taken == now()
