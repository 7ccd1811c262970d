"""The OLMoE cell's pieces at small sizes on the CPU: the reference check
(jobs/common.py:reference_check) passes for the program as it is and says no
to float8 weights, a bfloat16 router and a dropped assignment; the closed
forms of benchmark/needs_olmoe.py; the configuration against the catalog's
row; both new cells through run.py."""
import json
import os
import re

import numpy as np
import pytest

from benchmark import needs_olmoe, run
from benchmark.jobs import common
from benchmark.references import olmoe_pretrain as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmoe_1b_7b.pretrain_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# At the tests' widths and the builder's std-0.02 initialisation the experts
# add a hundredth of the residual stream, and nothing done to them shows in a
# loss. The published widths have them at a third of it (reference's
# docstring). So the down projections are scaled to carry as much here.
DOWN_SCALE = 40.0
SEED = 5        # no token's routing flips for it (asserted below)


def session(down_scale=1.0):
    import jax.numpy as jnp
    cell = run.load_cell(CELL, rehearsal=True)
    said = []
    s = common.Session(cell, SEED, said.append)
    for n in s.built["params"]:
        if n.endswith("_down_w"):
            v = s.scope.find_var(n)
            s.scope.set_var(n, (jnp.asarray(v, jnp.float32) * down_scale)
                            .astype(v.dtype))
    batch = s.builder.batch(s.model, s.params, np.random.RandomState(SEED))
    return s, batch, said


@pytest.mark.parametrize("down_scale", [1.0, DOWN_SCALE])
def test_program_agrees_with_the_plain_reference(down_scale):
    s, batch, said = session(down_scale)
    try:
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
        got = s.exe.run(s.built["test"], feed=batch,
                        fetch_list=s.built["expert_index"], scope=s.scope)
        want = reference.loss([s.scope.find_var(n) for n in
                               s.built["params"]], batch, s.model, s.params)
        share = reference.differing_share(np.stack(got),
                                          np.asarray(want["experts"]))
        assert share == 0.0 <= reference.flipped_share(s.model)
    finally:
        s.close()


def _float8_weights(s):
    import jax.numpy as jnp
    for n in s.built["params"]:
        v = s.scope.find_var(n)
        s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                        .astype(v.dtype))
    return lambda: None


def _patched(op_type, make):
    from paddle_tpu.core import registry
    opdef = registry.get(op_type)
    real = opdef.lower
    opdef.lower = make(real)

    def undo():
        opdef.lower = real
    return undo


def _bfloat16_router(s):
    import jax
    import jax.numpy as jnp

    def make(real):
        def lower(ctx, ins):
            x, w = ins["X"][0], ins["W"][0]
            logits = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
            prob = jax.nn.softmax(logits, -1)
            weight, index = jax.lax.top_k(prob, int(ctx.attr("k")))
            return {"Weight": [weight.astype(jnp.float32)],
                    "Index": [index.astype(jnp.int32)],
                    "Prob": [prob.astype(jnp.float32)],
                    "LogZ": [jax.nn.logsumexp(
                        logits.astype(jnp.float32), -1)]}
        return lower
    return _patched("moe_router", make)


def _one_assignment_dropped(s):
    def make(real):
        def lower(ctx, ins):        # the first sorted row never arrives
            ins = dict(ins, X=[ins["X"][0].at[0].set(0)])
            return real(ctx, ins)
        return lower
    return _patched("moe_combine", make)


@pytest.mark.parametrize("variant,down_scale", [
    (_float8_weights, 1.0), (_float8_weights, DOWN_SCALE),
    (_bfloat16_router, DOWN_SCALE), (_one_assignment_dropped, DOWN_SCALE)],
    ids=["float8_weights", "float8_weights_experts_scaled",
         "bfloat16_router", "one_assignment_dropped"])
def test_a_lower_precision_or_a_dropped_assignment_fails_the_check(
        variant, down_scale):
    """The program is altered while the reference keeps the original
    weights and its float32 routing: the comparison that decides ``correct``
    must say no."""
    s, batch, said = session(down_scale)
    originals = [s.scope.find_var(n) for n in s.built["params"]]
    real_loss = reference.loss
    undo = variant(s)
    reference.loss = lambda w, *a: real_loss(originals, *a)
    try:
        assert common.reference_check(s, batch) is False
        assert "FAILED" in said[-1]
    finally:
        reference.loss = real_loss
        undo()
        s.close()


def test_tolerance_sits_between_the_chip_readings_and_grows_with_depth():
    cell = run.load_cell(CELL, rehearsal=False)
    published = reference.tolerance(cell["model"])
    assert set(published) == {"loss", "each"}
    # PERF.md section 6 (PR 26): the program 1.40e-3 to 1.83e-3 of the
    # largest block on the chip, float8 weights 4.3e-3 to 6.7e-3
    assert 1.83e-3 * 1.4 < published["each"] < 4.3e-3 / 1.4
    assert published["loss"] <= 2e-4
    deeper = dict(cell["model"], num_hidden_layers=16)
    assert reference.tolerance(deeper)["each"] > published["each"]
    assert reference.differing_share([[0, 1], [2, 3]], [[1, 0], [3, 5]]) \
        == pytest.approx(0.25)
    assert 0.0052 * 1.5 < reference.flipped_share(cell["model"]) < 0.063 / 3


def test_reference_returns_positions_and_their_block_means():
    assert reference.check_block(4096) == 64
    assert reference.check_block(32) == 1
    cell = run.load_cell(CELL, rehearsal=True)
    cell["params"]["seq"] = 128                 # blocks of 2 positions
    s = common.Session(cell, SEED, lambda m: None)
    try:
        batch = s.builder.batch(s.model, s.params, np.random.RandomState(1))
        want = reference.loss([s.scope.find_var(n) for n in
                               s.built["params"]], batch, s.model, s.params)
        positions = np.asarray(want["positions"])
        assert positions.shape == (2 * 128,)
        np.testing.assert_allclose(np.asarray(want["each"]),
                                   positions.reshape(-1, 2).mean(1),
                                   rtol=1e-6)
        assert common.reference_check(s, batch) is True
    finally:
        s.close()


def test_closed_forms_match_the_issue_arithmetic():
    cell = run.load_cell(CELL, rehearsal=False)
    model, params = cell["model"], cell["params"]
    tokens = params["batch"] * params["seq"]
    layers = model["num_hidden_layers"]
    step = needs_olmoe.train_step(model, params)
    # 1.53 GFLOP a token forward + backward at two layers (ISSUE 26)
    per_token_two_layers = needs_olmoe.train_step(
        dict(model, num_hidden_layers=2), params)["per_token"]
    assert per_token_two_layers == pytest.approx(1.526e9, rel=2e-3)
    assert step["flops"] == pytest.approx(step["per_token"] * tokens)
    experts = needs_olmoe.moe_expert_matmul(model, params)
    assert experts["flops"] == layers * 3 * 2 * 3 * tokens * 8 * 2048 * 1024
    flash = needs_olmoe.flash_attention_causal(model, params)
    from benchmark import flops
    whole = flops.flash_attention(model, params)
    assert flash["flops"] * 2 == whole["flops"]         # half the square
    assert flash["bytes"] == whole["bytes"]
    # the experts are about two fifths of the model's FLOPs at two layers
    two = dict(model, num_hidden_layers=2)
    share = needs_olmoe.moe_expert_matmul(two, params)["flops"] \
        / needs_olmoe.train_step(two, params)["flops"]
    assert 0.38 < share < 0.42


def test_configuration_holds_every_number_of_the_catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    rows = [json.loads(ln) for ln in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "OLMoE-1B-7B-0125-Instruct")
    data = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "olmoe_1b_7b.json")))
    assert data["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items() if data.get(k, "?") != v]
    assert differ == data["reduced"] == ["num_hidden_layers"]
    assert data["num_hidden_layers"] in (1, 2)
    assert data["flops"] is None
    for key in ("router_aux_loss_coef", "router_z_loss_coef", "optimizer",
                "dtype", "data", "init"):
        assert key in data["assumed"]
    # what test_benchmark_files.py::test_config_files_resolve means by its
    # pattern: no width may be reduced. Its alternative "hidden" also hits
    # num_hidden_layers, a depth (conftest.py beside this file).
    width = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$"
                       r"|_rank$|head_|expansion|experts_per)")
    assert not [k for k in data["reduced"] if width.search(k)]


def _rehearse(cell):
    from test_benchmark_run import result_of, run_py
    r = run_py(["--workload", cell, "--seed", str(2 ** 31 + 7),
                "--seconds", "1", "--trace", "1", "--cpu-rehearsal"])
    return result_of(r)


def test_olmoe_cell_rehearses_with_its_metrics():
    result, lines = _rehearse(CELL)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    for name in ("moe.time_share", "moe_dispatch.time_share",
                 "norm_rope.time_share", "optimizer_adamw.time_share",
                 "attention.time_share", "matmul.time_share",
                 "embedding.time_share", "compile.trace_lower_s"):
        assert got[name]["value"] > 0, name
    # no chip, no peak: the roofline shares are left out, not raised
    for name in ("moe_expert_matmul_roofline", "step.model_flops_share",
                 "flash_attention_causal_roofline", "mfu",
                 "optimizer.time_share", "flash_attention_roofline"):
        assert name not in got
    assert any("moe_expert_matmul_grad" in ln for ln in lines)


def test_bert_s512_cell_rehearses():
    result, _ = _rehearse("bert_base.pretrain_s512")
    assert result["correct"] is True
    assert result["metrics"]["attention.time_share"]["value"] > 0
    assert "moe.time_share" not in result["metrics"]
