"""``tools/laguna_probe.py`` at the cell's rehearsal sizes on the CPU: the
readings the harness cannot take (it fetches the loss alone) run to their
end and say what they are for. The numbers of PERF.md come from the chip."""
import json

import pytest

from tools import laguna_probe


def probe(capsys, *argv):
    assert laguna_probe.main([*argv, "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_load_reads_the_held_share_and_the_rows_the_budget_dropped(capsys):
    got = probe(capsys, "load", "--steps", "24", "--ring", "4", "--lr",
                "1e-5")
    # the rehearsal holds 4 of its 8 routed experts: half at an even router
    # (64 tokens a step: single steps scatter)
    assert got["ring"] == 4 and len(got["share_by_20"]) == 2
    assert got["share_min"] <= got["share"] <= got["share_max"]
    assert 0.4 < got["share"] < 0.6
    assert got["budget"] == 96 and len(got["dropped"]) == 2
    # the count sums over the steps: it is at least what the fullest step
    # held over the budget
    assert sum(got["dropped"]) >= max(0, got["fullest_rows"] - got["budget"])
    assert got["loss_last"] < got["loss_first"]
    # the rows the token sums read, a layer: the held experts', cut to the
    # buffer
    assert got["buffer_rows"] == 96 and len(got["live_rows_mean"]) == 2
    assert all(0 < mean <= most <= 96 for mean, most in
               zip(got["live_rows_mean"], got["live_rows_max"]))
    assert max(got["live_rows_max"]) == min(got["fullest_rows"], 96)


def test_controls_run_at_the_seeded_state_and_say_what_fails(capsys):
    got = probe(capsys, "controls", "--seed", str(2 ** 31 + 5))
    assert got["as_it_is"]["ok"] is True
    assert got["float8_weights"]["ok"] is False
    assert got["float8_weights"]["each"] > 3 * got["as_it_is"]["each"]
    # at the rehearsal's widths the window, the gate and the scale show; the
    # rotary embedding's part needs the published widths (scores near zero
    # at 64 wide: the softmax is uniform whatever the positions)
    for mechanism in ("window", "gate", "routed_scale", "row_budget"):
        assert got["no_" + mechanism]["ok"] is False, mechanism
        assert got["no_" + mechanism]["each"] > 5 * got["as_it_is"]["each"]
    assert got["no_partial_rotary"]["each"] > 0


def test_gradients_of_every_leaf_against_the_reference(capsys):
    got = probe(capsys, "grads")
    assert got["the_programs"]["worst_l2"]["l2"] < 2e-2      # bfloat16 step
    assert got["the_programs"]["min_cos"] > 0.9999
    assert got["the_programs"]["flips"] == 0.0
    assert got["its_own"]["flips"] < 0.1


def test_without_takes_one_mechanism_out_and_keeps_the_parameters():
    from benchmark import run
    model = run.load_cell(laguna_probe.CELL, rehearsal=True)["model"]
    assert laguna_probe.without(model, "gate")["gating"] == "none"
    assert laguna_probe.without(model, "window")["sliding_window"] > 4096
    assert laguna_probe.without(model, "routed_scale")[
        "moe_routed_scaling_factor"] == 1.0
    changed = laguna_probe.without(model, "partial_rotary")
    assert changed["rope_parameters"]["full_attention"][
        "partial_rotary_factor"] == 1
    assert model["rope_parameters"]["full_attention"][
        "partial_rotary_factor"] == 0.5         # the cell's own is untouched
    assert laguna_probe.without(model, "row_budget")[
        "moe_row_budget"] == model["moe_row_budget"] // 10
    with pytest.raises(ValueError):
        laguna_probe.without(model, "norm")


@pytest.mark.parametrize("cell,tilt", [
    ("laguna_s_2_1", 0.0), ("laguna_s_2_1", 0.6), ("qwen3_next_80b_a3b", 0.0),
    ("glm_4_7_flash", 0.0), ("lfm2_8b_a1b", 0.0), ("olmoe_1b_7b", 0.0)])
def test_sums_times_the_token_sums_at_any_expert_cells_shape(capsys, cell,
                                                             tilt):
    """The rehearsal's width (64) is not the kernel's, so only the composed
    form is timed here; the shapes are the cell's and a tilted router fills
    the held experts' rows."""
    got = probe(capsys, "sums", "--cell", cell + ".pretrain_s4096", "--tilt",
                str(tilt))
    assert got["mode"] == "sums" and got["composed_ms"] > 0
    assert "kernel_ms" not in got and got["width"] % 128
    assert got["live_rows"] <= got["buffer_rows"] <= got["tokens"] * got["k"]
    even = got["tokens"] * got["k"] * got["held"] / got["routed"]
    if tilt:
        assert got["live_rows"] > 1.2 * min(even, got["buffer_rows"]) \
            or got["live_rows"] == got["buffer_rows"]
    else:
        assert 0.6 * even < got["live_rows"] <= max(1.4 * even,
                                                    got["buffer_rows"])
