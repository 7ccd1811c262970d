"""``tools/lfm2_probe.py`` at the cell's rehearsal sizes on the CPU: the
three readings the harness cannot take (it fetches the loss alone) run to
their end and say what they are for. The numbers of PERF.md come from the
chip."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.references import lfm2_pretrain as reference
from tools import lfm2_probe


def probe(capsys, *argv):
    assert lfm2_probe.main([*argv, "--rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_load_reads_the_held_share_and_holds_the_bias_to_its_rule(capsys):
    got = probe(capsys, "load", "--steps", "24", "--ring", "4")
    # the rehearsal holds 4 of its 8 routed experts: half at an even router
    assert got["ring"] == 4 and len(got["share_by_20"]) == 2
    # (64 tokens a step: single steps scatter)
    assert got["share_min"] <= got["share"] <= got["share_max"]
    assert 0.4 < got["share"] < 0.6
    assert got["bias_error"] == 0.0 and got["reference_after"] is True


def test_controls_run_at_the_seeded_state_and_float8_fails(capsys):
    got = probe(capsys, "controls", "--seed", str(2 ** 31 + 5))
    assert got["as_it_is"]["ok"] is True
    assert got["float8_weights"]["ok"] is False
    assert got["float8_weights"]["each"] > 3 * got["as_it_is"]["each"]
    # at these sizes the float32-stated parts in bfloat16 pass, as they do
    # on the chip: the reading is what the probe is for
    assert got["bfloat16_parts"]["each"] > 0


def test_gradients_of_every_leaf_against_the_reference(capsys):
    got = probe(capsys, "grads")
    for routing in ("its_own", "the_programs"):
        assert got[routing]["worst_l2"]["l2"] < 2e-2     # bfloat16 step
        assert got[routing]["min_cos"] > 0.9999


def test_reference_takes_the_choice_of_experts_as_given():
    """Handed its own choice the reference is itself; handed another it
    routes by that (the probe's gradient comparison along the program's
    choice rests on it)."""
    cell = run.load_cell(lfm2_probe.CELL, rehearsal=True)
    model = dict(cell["model"], dtype="float32")
    from benchmark.programs import lfm2_pretrain as builder
    import paddle_tpu as fluid
    built = builder.build(model, cell["params"])
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(built["startup"], scope=scope)
    weights = [jnp.asarray(np.array(scope.find_var(n)), jnp.float32)
               for n in built["params"]]
    exe.close()
    batch = builder.batch(model, cell["params"], np.random.RandomState(3))
    free = reference.forward(weights, batch, model)
    same = reference.forward(weights, batch, model, chosen=free["experts"])
    np.testing.assert_allclose(same["loss"], free["loss"], rtol=1e-6)
    np.testing.assert_array_equal(same["load"], free["load"])
    routed = model["num_experts_routed"]
    other = (free["experts"] + 1) % routed
    moved = reference.forward(weights, batch, model, chosen=other)
    np.testing.assert_array_equal(moved["experts"], jnp.sort(other, axis=-1))
    assert float(jnp.abs(moved["positions"] - free["positions"]).max()) > 0
