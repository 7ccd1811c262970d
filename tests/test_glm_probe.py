"""``tools/glm_probe.py`` at the cell's rehearsal sizes on the CPU: the
readings the harness cannot take run to their end and say what they are
for, through ``tools/laguna_probe.py``'s shared functions. The numbers of
PERF.md come from the chip."""
import json
import math

import pytest

from tools import glm_probe as probe_tool


def probe(capsys, *argv):
    assert probe_tool.main([*argv, "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_controls_run_at_the_seeded_state_and_say_what_shows(capsys):
    got = probe(capsys, "controls", "--seed", str(2 ** 31 + 5))
    assert got["as_it_is"]["ok"] is True
    assert got["float8_weights"]["each"] > 2 * got["as_it_is"]["each"]
    # at the rehearsal's widths the latent norms, the module's norm of the
    # embedding and its second label show, in the module's block means; the
    # routed scale and the row budget need the published widths' norms (a
    # layer's reads 0.01 here beside a cross-entropy of 6.5), the
    # rotation and the softmax scale sharpened scores
    # (tests/test_decoder_glm.py)
    for mechanism in ("latent_norms", "e_norm", "second_label"):
        control = got["no_" + mechanism]
        assert control["ok"] is False, mechanism
        assert control["each"] > 5 * got["as_it_is"]["each"]
        assert control["parts"]["mtp_blocks"] == pytest.approx(
            control["each"], rel=1e-3)
    # of its own value a layer's norm loses the scale's 1 - 1 / 1.8, and
    # most of itself under an eighth of the budget
    assert all(0.4 < r < 0.5 for r in
               got["no_routed_scale"]["parts"]["held_norm_rel"])
    assert all(0.4 < r < 0.95 for r in
               got["no_row_budget"]["parts"]["held_norm_rel"])
    assert max(got["as_it_is"]["parts"]["held_norm_rel"]) < 0.05
    for mechanism in ("routed_scale", "row_budget", "k_r_rotation",
                      "softmax_scale", "bf16_latent_norms"):
        assert got["no_" + mechanism]["each"] > 0


def test_parts_cut_the_check_where_its_entries_lie(capsys):
    got = probe(capsys, "parts", "--seed", str(2 ** 31 + 5))
    assert len(got["held_norms"]) == len(got["held_norm_rel"]) == 2
    worst = max(got[k] for k in ("trunk_blocks", "mtp_ce", "mtp_blocks",
                                 "held_norm"))
    assert 0 < worst < 2.2e-3


def test_without_takes_one_mechanism_out_and_keeps_the_parameters():
    from benchmark import run
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.ops import decoder_ops
    model = run.load_cell(probe_tool.CELL, rehearsal=True)["model"]
    assert probe_tool.without(model, "routed_scale")[
        "routed_scaling_factor"] == 1.0
    assert probe_tool.without(model, "softmax_scale")[
        "attention_multiplier"] == 1 / math.sqrt(model["qk_nope_head_dim"])
    assert model["routed_scaling_factor"] == 1.8    # the cell's own untouched
    assert probe_tool.without(model, "row_budget")[
        "moe_row_budget"] == model["moe_row_budget"] // 8
    assert probe_tool.without(model, "latent_norms") == model   # patched's
    with pytest.raises(ValueError):
        probe_tool.without(model, "norm")
    norm, rows, build = (decoder_lm._norm, decoder_ops._rope_rows,
                         decoder_lm.build)
    for mechanism, owner, name, was in (
            ("latent_norms", decoder_lm, "_norm", norm),
            ("e_norm", decoder_lm, "_norm", norm),
            ("k_r_rotation", decoder_ops, "_rope_rows", rows),
            ("second_label", decoder_lm, "build", build)):
        with probe_tool.patched(mechanism):
            assert getattr(owner, name) is not was
        assert getattr(owner, name) is was
