"""``tools/ouro_probe.py`` at the cell's rehearsal sizes on the CPU: the three
readings the harness cannot take (it fetches the loss alone) run to their
end and say what they are for. The numbers of PERF.md come from the chip."""
import json

from tools import ouro_probe


def probe(capsys, *argv):
    assert ouro_probe.main([*argv, "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_load_reads_what_the_loop_keeps_with_and_without_recomputation(
        capsys):
    got = probe(capsys, "load", "--steps", "2")
    layer, none = got["runs"]
    assert (layer["recompute"], none["recompute"]) == ("layer", "none")
    assert layer["loop_stack_lowerings"] == none["loop_stack_lowerings"] == 1
    assert layer["xla"]["temp"] < 0.5 * none["xla"]["temp"]
    assert layer["loop_kept_bytes"] < 0.1 * none["loop_kept_bytes"]
    assert abs(layer["loss"] - none["loss"]) < 1e-3 * none["loss"]


def test_controls_go_through_the_harness_check_and_float8_fails(capsys):
    """Each control is ``common.reference_check``'s own verdict and line;
    under float8 weights the program alone is lowered, the reference reads
    the weights as they were."""
    got = probe(capsys, "controls", "--seed", str(2 ** 31 + 5))
    assert got["as_it_is"]["ok"] is True
    assert got["float8_weights"]["ok"] is False
    assert got["float8_weights"]["each"] > 3 * got["as_it_is"]["each"]
    assert got["seeded_gate"]["ok"] is True
    # the float32-stated parts in bfloat16 move the reference by about the
    # program's own error and far under the limit: the check does not hold
    # them (tests/test_decoder_ouro.py does, at float32)
    assert 0 < got["bfloat16_inside"]["each"] < got["tolerance"]["each"]


def test_gradients_of_every_leaf_against_the_reference(capsys):
    got = probe(capsys, "grads")
    assert got["ok"] is True
    assert got["worst_l2"]["l2"] < 3e-2 and got["min_cos"] > 0.9995
    assert abs(got["loss"] - got["reference_loss"]) < 1e-3
