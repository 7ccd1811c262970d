"""An expert layer's token sums as the kernel of ``ops/pallas_moe_rows.py``
(in the interpreter: tests/conftest.py) against the composed forms of
``ops/decoder_ops.py`` it replaces on a TPU -- ``_sum_slots`` without a row
budget, ``_add_rows`` under one: by dtype, top-k and width, with all experts
held or a part (from expert 0 or wrapped), under even and tilted routers,
with the held rows inside, at and over the budget, and with none."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import decoder_ops, pallas_moe_rows

E = 8


def routed(T, k, first, held, budget, tilt, seed=0, E=E):
    """What ``moe_dispatch`` hands the sums under a seeded router over E
    experts, ``held`` of them from ``first`` on held here (``tilt``: that
    share of the tokens choose the first held expert besides): order, slot,
    the held groups' bounds, the buffer's rows and the rows the budget
    dropped."""
    rng = np.random.RandomState(seed)
    logits = rng.randn(T, E)
    logits[rng.rand(T) < tilt, first] += 100.0
    flat = (np.argsort(-logits, axis=1)[:, :k].reshape(-1) - first) % E
    order = np.argsort(flat, kind="stable").astype(np.int32)
    slot = np.empty_like(order)
    slot[order] = np.arange(order.size, dtype=np.int32)
    rows = budget or T * k
    ends = np.cumsum(np.bincount(flat, minlength=E)[:held])
    bounds = np.minimum(np.concatenate([[0], ends]), rows).astype(np.int32)
    return (order[:rows], slot.reshape(T, k), bounds, rows,
            max(int(ends[-1]) - rows, 0))


def sorted_rows(rows, width, live, dtype, seed=1):
    """A buffer whose rows from ``live`` on are what the grouped products
    leave there: zero."""
    x = np.random.RandomState(seed).randn(rows, width).astype("float32")
    x[live:] = 0
    return jnp.asarray(x, dtype)


def both(x, order, slot, bounds, budget):
    composed = decoder_ops._add_rows if budget else decoder_ops._sum_slots
    return (pallas_moe_rows.token_sums(x, jnp.asarray(slot),
                                       jnp.asarray(bounds), interpret=True),
            composed(x, jnp.asarray(order), jnp.asarray(slot)))


def agree(got, want, dtype):
    """Float32: to its rounding (the kernel adds a token's rows in sorted
    order, the composed forms in slot order). bfloat16: the float32 sums
    differ by as little, so their roundings agree or are neighbours."""
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    tol = 1e-6 if dtype == "float32" else 2 ** -7
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("budgeted", [False, True], ids=["all_rows", "budget"])
@pytest.mark.parametrize("width", [128, 384])
@pytest.mark.parametrize("k", [2, 4, 10])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_equals_the_composed_sums(dtype, k, width, budgeted):
    T, experts = 64, 8 if k < 8 else 16     # top-10 of 16 experts, 4 held
    held = experts // 4
    budget = T * k // 2 if budgeted else None
    order, slot, bounds, rows, _ = routed(T, k, 0, held, budget, 0.0,
                                          E=experts)
    x = sorted_rows(rows, width, bounds[-1], dtype)
    agree(*both(x, order, slot, bounds, budget), dtype)


def test_two_slots_a_token_are_bit_equal_whatever_their_order():
    """A sum of two float32 addends does not depend on their order: with
    top-2 the kernel's sums are the composed form's bit for bit."""
    order, slot, bounds, rows, _ = routed(64, 2, 0, E, None, 0.0)
    got, want = both(sorted_rows(rows, 256, rows, "float32"), order, slot,
                     bounds, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("live", ["none", "below", "at", "over"])
def test_held_rows_against_a_budget(live):
    """``live`` = 0 (no held expert got a row: the sums are zero and no row
    is read), below the budget, exactly the buffer, and held rows
    overflowing it: the dropped rows add nothing and are counted."""
    T, k, held = 64, 4, 2
    order, slot, bounds, _, _ = routed(T, k, 0, held, None, 0.0)
    received = int(bounds[-1])
    budget = {"none": 64, "below": received + 16, "at": received,
              "over": received - 24}[live] // 8 * 8
    if live == "at":    # a multiple of 8 rows: cut the router's last rows
        budget = received // 8 * 8
    order, slot, bounds, rows, dropped = routed(T, k, 0, held, budget, 0.0)
    if live == "none":
        bounds = np.zeros_like(bounds)
    assert dropped == max(received - budget, 0)
    assert (dropped > 0) == (live in ("over", "at") and received > budget)
    x = sorted_rows(rows, 128, bounds[-1], "float32")
    got, want = both(x, order, slot, bounds, budget)
    if live == "none":
        np.testing.assert_array_equal(np.asarray(got), 0.0)
    else:
        agree(got, want, "float32")
        kept = np.asarray(slot) < bounds[-1]
        assert kept.sum() == bounds[-1]


@pytest.mark.parametrize("first", [3, 6], ids=["inside", "wrapped"])
def test_held_experts_from_another_first_expert(first):
    """``first_expert`` > 0: the sort starts there and wraps (experts 6, 7,
    0 are the groups 0, 1, 2), and the bounds are of the sorted order."""
    order, slot, bounds, rows, _ = routed(64, 4, first, 3, None, 0.0)
    x = sorted_rows(rows, 128, bounds[-1], "bfloat16")
    agree(*both(x, order, slot, bounds, None), "bfloat16")


@pytest.mark.parametrize("budgeted", [False, True], ids=["all_rows", "budget"])
def test_a_tilted_router_takes_several_passes(budgeted, monkeypatch):
    """One held expert draws 60% of the tokens: a block's run of its rows
    is longer than a buffer (``PASS_ROWS`` cut to 128 here), so the block
    takes several passes, each added to the same sums."""
    monkeypatch.setattr(pallas_moe_rows, "PASS_ROWS", 128)
    pallas_moe_rows.token_sums.clear_cache()
    T, k, held = 256, 4, 2
    budget = T * k // 2 if budgeted else None
    order, slot, bounds, rows, _ = routed(T, k, 0, held, budget, 0.6)
    assert bounds[1] > 0.55 * T
    x = sorted_rows(rows, 128, bounds[-1], "float32")
    agree(*both(x, order, slot, bounds, budget), "float32")
    pallas_moe_rows.token_sums.clear_cache()


def test_rows_past_the_held_experts_are_not_read():
    """The padding past the held rows' last slab may hold anything, and what
    it holds inside that slab is zeroed: the kernel's sums do not change."""
    order, slot, bounds, rows, _ = routed(64, 4, 0, 2, None, 0.0)
    x = sorted_rows(rows, 128, bounds[-1], "float32")
    dirty = x.at[int(bounds[-1]):].set(jnp.nan)
    got = pallas_moe_rows.token_sums(dirty, jnp.asarray(slot),
                                     jnp.asarray(bounds), interpret=True)
    agree(got, both(x, order, slot, bounds, None)[1], "float32")


@pytest.mark.parametrize("tokens,rows,width,dtype,fits", [
    (8192, 20480, 2048, "bfloat16", True),
    (4096, 5120, 3072, "bfloat16", True),
    (64, 128, 128, "float32", True),
    (64, 128, 64, "float32", False),        # not whole vregs of lanes
    (60, 120, 128, "float32", False),       # tokens in no whole block
    (64, 120, 128, "bfloat16", False),      # the buffer in no whole slabs
    (64, 128, 128, "int8", False)])
def test_what_the_kernel_takes(tokens, rows, width, dtype, fits):
    assert pallas_moe_rows.supports(tokens, rows, width, dtype) is fits


def test_run_starts_are_where_each_blocks_rows_begin():
    order, slot, bounds, rows, _ = routed(64, 4, 0, 3, None, 0.0)
    starts = np.asarray(pallas_moe_rows.run_starts(
        jnp.asarray(slot), jnp.asarray(bounds), 16)).reshape(5, 3)
    np.testing.assert_array_equal(starts[0], bounds[:-1])
    np.testing.assert_array_equal(starts[-1], bounds[1:])
    token = order // 4
    for b in range(4):
        for g in range(3):      # a run is one block's rows of one group
            run = token[starts[b, g]:starts[b + 1, g]]
            assert ((run >= 16 * b) & (run < 16 * b + 16)).all()


def test_kernel_ops_metric_counts_three_an_expert_layer():
    """``moe_rows.kernel_ops`` (a data file on ``registry_count``) agrees
    with its ``BENCHMARK.json`` entry, the last of the list, is the five
    cells' with expert layers, and over the counters a cell's two compiled
    programs add on the chip (the test clone's combine, the train step's
    combine and dispatch-grad: 3 a layer) reads 12 for four layers; composed
    ops are left out, and a parent without the counter reads None."""
    import importlib
    import json
    import os
    from benchmark import run
    from paddle_tpu.observability import lowerings
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec = json.load(open(os.path.join(
        root, "benchmark", "layer_metrics", "moe_rows.kernel_ops.json")))
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "moe_rows.kernel_ops")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["name"] == "moe_rows.kernel_ops" and entry["layer"] == "moe"
    cells = [w["name"] for w in bench["workloads"] if w["config"] in (
        "olmoe_1b_7b", "lfm2_8b_a1b", "laguna_s_2_1", "qwen3_next_80b_a3b",
        "glm_4_7_flash")]
    # the five cells PR 50 gave it, then every expert cell added since
    assert entry["workloads"][:5] == cells and len(cells) == 5
    for name in cells:
        assert "moe_rows.kernel_ops" in [
            m["name"] for m in run.load_cell(name, False)["per_layer"]]
    reduce = importlib.import_module(
        f"benchmark.reducers.{spec['reducer']}").reduce
    before = reduce(spec, None) or 0.0
    for program, ops, impl in (
            ("pr50_test_clone", ("combine",), "pallas"),
            ("pr50_train_step", ("combine", "dispatch_grad"), "pallas"),
            ("pr50_mesh", ("combine", "dispatch_grad"), "composed")):
        notes = {}
        for salt in range(4):
            for op in ops:
                lowerings.note(notes, salt, "moe_rows_lowering_total", 1,
                               dict(impl=impl, op=op, bound="held"))
        lowerings.publish(notes, program)
    assert reduce(spec, None) - before == 12.0
    assert reduce(dict(spec, match="pr50_no_such_counter"), None) is None
