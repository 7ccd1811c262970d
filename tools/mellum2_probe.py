"""What the benchmark's harness does not fetch from the cell
``mellum2_12b_a2_5b.pretrain_s4096_ep4`` (it reads the loss alone): the
builder's chip readings of PERF.md section 6, PR 55. On a four-chip TPU host
through ``chiprun --chips 4 -- python3 tools/mellum2_probe.py <mode> ...``
(``share`` on one chip); ``--rehearsal`` runs the data files' rehearsal
sizes on four CPU devices (``XLA_FLAGS=--xla_force_host_platform_device_
count=4``; a debug run: no device number).

``load``      ``--steps`` train steps of the cell: the rows each chip's
              experts received a layer against the receive buffer (from the
              fetched loads), the rows dropped since startup (ISSUE 55
              asked for 0; at its recipe they are not: PERF.md section 6),
              the state each device holds, the allocator's peak, and the
              kernel families' lowerings under the mesh.
``wire``      the cell under each of ``--impls`` (ragged / padded): ms a
              step over ``--steps`` steps after warm-up and the rows
              dropped; then the exchange alone at the cell's shapes under a
              uniformly random router, each way: ms and GB/s a chip.
``controls``  ``tools/laguna_probe.py``'s on this cell, every verdict
              ``benchmark.jobs.common.reference_check``'s own: the program
              as it is; float8 (e4m3) weights in the program's place; the
              exchange dropped (a chip's experts multiply the rows of its
              own tokens only: must FAIL), the window at 512, YaRN's
              ``attention_factor`` at 1, ``norm_topk_prob`` off -- each
              taken out of the PROGRAM while the reference keeps it -- and
              a bfloat16 router in the REFERENCE.
``readings``  the two readings the check's limit is set from, over
              ``--seeds``: as it is, and float8 weights in the program's
              place.
``passes``    one chip: the passes of a crossing around the wire, each
              timed apart at the cell's shapes (``[budget, H]`` bfloat16,
              the plan of a chip's four sources' sorts under a uniformly
              random router and under one ``--tilt`` of whose tokens choose
              expert 0 besides): the index arrays the composed change of
              order builds (the default ``searchsorted`` and
              ``compare_all``), its two gathers, ``take``, the zero fill;
              the kernel of ``ops/pallas_exchange_rows.py`` both ways
              (``--rows-plans``: also at these block x piece rows) and
              whether its
              output is the gather's bit for bit on the live rows and zero
              behind them; the router weights' crossing as a gather and as
              lanes through the kernel.
``share``     one chip's share of the same program without the exchange
              (``experts_held=(0, 16)``, the chip's 2 sequences, its
              vocabulary slice): ms a step; the cell's step less this is
              what the exchange, the whole vocabulary and the gradient sums
              cost.
"""
from __future__ import annotations

import contextlib
import copy
import importlib
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools import laguna_probe  # noqa: E402
from tools.laguna_probe import say  # noqa: E402

CELL = "mellum2_12b_a2_5b.pretrain_s4096_ep4"
MECHANISMS = ("exchange", "window", "yarn_factor", "norm_topk",
              "bf16_router")


def without(model: dict, mechanism: str) -> dict:
    """The configuration with one mechanism changed; parameters keep their
    names and shapes, so the program runs on the cell's own weights."""
    model = copy.deepcopy(model)
    if mechanism == "window":
        model["sliding_window"] //= 2
    elif mechanism == "yarn_factor":
        model["rope_parameters"]["full_attention"]["attention_factor"] = 1.0
    elif mechanism == "norm_topk":
        model["norm_topk_prob"] = False
    elif mechanism not in ("exchange", "bf16_router"):
        raise ValueError(mechanism)
    return model


@contextlib.contextmanager
def patched(mechanism: str):
    """What no configuration key takes out: the exchange (every chip keeps
    its own tokens' rows only: the rows that came from another chip are
    zeroed where they arrive), and the reference's router in bfloat16."""
    from paddle_tpu.ops import collective
    from benchmark.references import mellum2_pretrain as reference
    if mechanism == "exchange":
        import jax.numpy as jnp

        class OwnRowsOnly(collective.RowExchange):
            def out(self, take, sorted_rows):
                got = super().out(take, sorted_rows)
                source = jnp.searchsorted(self.in_off, self.to_expert_major,
                                          side="right") - 1
                mine = (source == self.me).reshape(
                    (-1,) + (1,) * (got.ndim - 1))
                return jnp.where(mine, got, jnp.zeros((), got.dtype))
        real, collective.RowExchange = collective.RowExchange, OwnRowsOnly
        try:
            yield
        finally:
            collective.RowExchange = real
    elif mechanism == "bf16_router":
        import jax.numpy as jnp
        plain = reference.router_logits
        reference.router_logits = lambda x, w: (
            x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
        ).astype(jnp.float32)       # input and product rounded to bfloat16
        reference._layer_call.cache_clear()
        try:
            yield
        finally:
            reference.router_logits = plain
            reference._layer_call.cache_clear()
    else:
        yield


def controls(args) -> dict:
    return laguna_probe.controls(args, without=without,
                                 mechanisms=MECHANISMS, patched=patched)


def readings(args) -> dict:
    """The two readings the check's limit is set from, over ``--seeds``: the
    program as it is and float8 (e4m3) weights in its place, each seed a
    session of its own, at seeded weights."""
    result = {"mode": "readings", "seeds": list(args.seeds)}
    for seed in args.seeds:
        args.seed = seed
        got = laguna_probe.controls(args, mechanisms=())
        result[str(seed)] = {k: got[k] for k in ("as_it_is",
                                                 "float8_weights")}
    sound = [v["as_it_is"] for k, v in result.items() if k.isdigit()]
    float8 = [v["float8_weights"] for k, v in result.items() if k.isdigit()]
    result.update(
        as_it_is_max=max(v["each"] for v in sound),
        float8_min=min(v["each"] for v in float8),
        loss_as_it_is_max=max(v["loss"] for v in sound),
        loss_float8_min=min(v["loss"] for v in float8))
    say(f"as it is, largest each {result['as_it_is_max']:.3e} (mean loss "
        f"{result['loss_as_it_is_max']:.3e}); float8 weights, smallest each "
        f"{result['float8_min']:.3e} (mean loss "
        f"{result['loss_float8_min']:.3e}) over {len(sound)} seeds")
    return result


def device_state_gb(scope) -> list:
    """Bytes of the scope's arrays each device holds, GB by device id."""
    import jax
    held = {}
    for name in scope.var_names():
        v = scope.find_var(name)
        if isinstance(v, jax.Array):
            for shard in v.addressable_shards:
                held[shard.device.id] = held.get(shard.device.id, 0) + \
                    shard.data.nbytes
    return [held[d] / 1e9 for d in sorted(held)]


def lowerings() -> dict:
    """Every ``*_lowering_total`` child of the train step's compile, by
    family and labels (but ``program``)."""
    from paddle_tpu.observability.metrics import REGISTRY
    out = {}
    for family in REGISTRY.collect():
        if not family.name.endswith("_lowering_total"):
            continue
        for labels, child in family.items():
            key = ",".join(f"{k}={v}" for k, v in labels if k != "program")
            out[f"{family.name}{{{key}}}"] = out.get(
                f"{family.name}{{{key}}}", 0) + child.value
    return out


def steps_of(cell, seed, steps, fetch_index=False) -> dict:
    """A session of the cell: warm-up, then ``steps`` steps timed with the
    loss read at the end only; the loads of the last step."""
    from benchmark import probe
    from benchmark.jobs import common, train_feed
    cell = copy.deepcopy(cell)
    s = train_feed.setup(cell, seed, say)
    chips = cell["chips"]
    names = s.built["expert_load"] + s.built["expert_dropped"] + (
        s.built["expert_index"] if fetch_index else [])
    n = len(s.built["expert_load"])

    def received():     # one more step, with the loads fetched
        got = s.exe.run(s.program, feed=s.ring[s.step % len(s.ring)],
                        fetch_list=names, scope=s.scope)
        s.step += 1
        load = np.stack(got[:n]).astype(np.int64)           # [layers, E]
        return got, load.reshape(n, chips, -1).sum(-1)      # [layers, chips]
    _, received_first = received()
    s.sync()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = s.exe.run(s.program, feed=s.ring[s.step % len(s.ring)],
                        fetch_list=[s.loss], scope=s.scope,
                        return_numpy=False)
        s.step += 1
    s.sync()
    step_ms = (time.perf_counter() - t0) / steps * 1e3
    got, received = received()
    result = {
        "step_ms": step_ms, "loss_last": common.loss_value(out[0]),
        "loss_first": s.first_loss, "reference": s.checks["reference"],
        "rows_received_by_layer_and_chip": received.tolist(),
        "rows_received_at_the_first_step": received_first.tolist(),
        "budget": s.model.get("moe_row_budget"),
        "dropped_since_startup": [int(np.asarray(d).reshape(-1)[0])
                                  for d in got[n:2 * n]],
        "state_gb_by_device": device_state_gb(s.scope),
        "peak_gb": probe.peak_bytes(s.devices) / 1e9,
        "tokens_per_s": s.units_per_step / step_ms * 1e3}
    if fetch_index:     # rows by (source chip, owner chip): the padded
        k = s.model["num_experts_per_tok"]                  # wire's parts
        per = s.model["num_experts"] // chips
        pairs = []
        for index in got[2 * n:]:
            owner = np.asarray(index).reshape(chips, -1, k) // per
            pairs.append([[int((owner[c] == o).sum()) for o in range(chips)]
                          for c in range(chips)])
        result["rows_by_source_and_owner"] = pairs
    s.close()
    return result


def load(args) -> dict:
    cell = laguna_probe.load_cell(args)
    result = {"mode": "load", "seed": args.seed, "steps": args.steps,
              **steps_of(cell, args.seed, args.steps, fetch_index=True),
              "lowerings": lowerings()}
    say(f"{result['step_ms']:.2f} ms a step ({result['tokens_per_s']:.0f} "
        f"tokens/s; the loss read at the end only); rows received a layer "
        f"and chip at the first step after warm-up "
        f"{result['rows_received_at_the_first_step']}, at the last "
        f"{result['rows_received_by_layer_and_chip']} of a buffer "
        f"of {result['budget']}; dropped since startup "
        f"{result['dropped_since_startup']}; state by device "
        f"{[round(g, 3) for g in result['state_gb_by_device']]} GB, the "
        f"allocator's peak {result['peak_gb']:.3f} GB")
    for key, value in sorted(result["lowerings"].items()):
        say(f"  {key} {value:g}")
    return result


def exchange_alone(cell, impl: str, reps: int = 10) -> dict:
    """The exchange alone at the cell's shapes, a uniformly random router:
    ms a crossing each way (the packing and the laying out included) and
    the GB/s a chip's links carry, counted one way."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.ops.collective import RowExchange
    from paddle_tpu.ops.decoder_ops import sort_by_expert
    n = cell["chips"]
    model, params = cell["model"], cell["params"]
    T, k = params["batch"] * params["seq"] // n, model["num_experts_per_tok"]
    E, H = model["num_experts"], model["hidden_size"]
    budget = model["moe_row_budget"]
    mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
    rng = np.random.RandomState(0)
    index = jnp.asarray(rng.randint(0, E, (n * T, k)), jnp.int32)
    x = jnp.asarray(rng.randn(n * T, H), jnp.bfloat16)

    def sort(index):
        order, _, count = sort_by_expert(index, E)
        return order, jax.lax.all_gather(count, "dp")

    def out(x, index):
        order, cnt = sort(index)
        return RowExchange(cnt, "dp", n, budget, impl).out(
            lambda at: x[order[at] // k], order.shape[0])

    def back(y, index):
        order, cnt = sort(index)
        return RowExchange(cnt, "dp", n, budget, impl).back(
            y, order.shape[0])

    cut = P("dp")
    put = lambda a: jax.device_put(a, NamedSharding(mesh, cut))  # noqa: E731
    x, index = put(x), put(index)
    result = {}
    y = None
    for name, fn, arg in (("out", out, x), ("back", back, None)):
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(cut, cut),
                                  out_specs=cut, check_vma=False))
        arg = y if arg is None else arg
        got = jax.block_until_ready(f(arg, index))
        t0 = time.perf_counter()
        for _ in range(reps):
            got = f(arg, index)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - t0) / reps * 1e3
        y = got if name == "out" else y
        sent = T * k * (n - 1) / n * H * 2
        result[name] = {"ms": ms, "gb_per_s_one_way": sent / ms / 1e6}
    return result


def wire(args) -> dict:
    from paddle_tpu.ops import collective
    cell = laguna_probe.load_cell(args)
    result = {"mode": "wire", "seed": args.seed, "steps": args.steps}
    chosen = collective.exchange_impl
    for impl in args.impls:
        collective.exchange_impl = lambda impl=impl: impl
        try:
            got = steps_of(cell, args.seed, args.steps)
            got["alone"] = exchange_alone(cell, impl)
        except Exception as e:      # noqa: BLE001  a wire the compiler
            got = {"error": f"{type(e).__name__}: {e}"[:600]}   # refuses
        finally:
            collective.exchange_impl = chosen
        result[impl] = got
        say(f"{impl}: {got}")
    return result


def passes(args) -> dict:
    """The passes of a crossing around the wire, apart, on one chip (the
    module docstring's ``passes``)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_exchange_rows as kernel_rows
    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.ops.collective import RowExchange
    from paddle_tpu.ops.decoder_ops import sort_by_expert
    ms = laguna_probe._ms
    cell = laguna_probe.load_cell(args)
    n, model, params = cell["chips"], cell["model"], cell["params"]
    T, k = params["batch"] * params["seq"] // n, model["num_experts_per_tok"]
    E, H = model["num_experts"], model["hidden_size"]
    budget = model["moe_row_budget"]
    if args.rehearsal:      # a debug run: a width and a buffer the kernel
        H, budget = 128, 256 * n                                # takes
    dtype = jnp.bfloat16
    interpret = pallas_mode.interpret() or (
        args.rehearsal and not pallas_mode.on_tpu())
    result = {"mode": "passes", "budget": budget, "width": H,
              "rows_sorted": T * k, "device": jax.devices()[0].device_kind}
    rng = np.random.RandomState(args.seed % 2 ** 31)
    x = jnp.asarray(rng.randn(T, H), dtype)
    got = jnp.asarray(rng.randn(budget, H), dtype)
    weights = jnp.asarray(rng.rand(budget), jnp.float32)

    def plan_of(cnt, kernel=None):
        return RowExchange(cnt, "dp", n, budget, "ragged", kernel=kernel,
                           me=jnp.int32(0))

    def scan_indices(cnt):
        """The index arrays as the parent of PR 56 built them: the default
        ``searchsorted`` (a loop of element gathers)."""
        plan = plan_of(cnt)
        rows = jnp.arange(budget, dtype=jnp.int32)
        sm, em, ln = (v.T.reshape(-1) for v in plan.segments)
        seg = jnp.minimum(jnp.searchsorted(em + ln, rows, side="right"),
                          ln.shape[0] - 1)
        to_expert = jnp.clip(sm[seg] + rows - em[seg], 0, budget - 1)
        sm, em, _ = (v.reshape(-1) for v in plan.segments)
        seg = jnp.maximum(jnp.searchsorted(sm, rows, side="right") - 1, 0)
        return to_expert, jnp.clip(em[seg] + rows - sm[seg], 0, budget - 1)

    for name, tilt in (("uniform", 0.0), ("skewed", args.tilt or 0.3)):
        logits = rng.randn(n, T, E)
        logits[rng.rand(n, T) < tilt, 0] += 100.0
        index = jnp.asarray(np.argsort(-logits, axis=-1)[..., :k], jnp.int32)
        sorts = [sort_by_expert(index[j], E) for j in range(n)]
        order, cnt = sorts[0][0], jnp.stack([c for _, _, c in sorts])
        plan = plan_of(cnt)
        live = int(plan.live)
        to_expert, to_source = plan.to_expert_major, plan.to_source_major
        scan = jax.jit(scan_indices)(cnt)
        here = {
            "live_rows": live, "dropped": int(plan.dropped),
            "segment_rows_min_max": [int(plan.segments[2].min()),
                                     int(plan.segments[2].max())],
            "indices_agree": bool(jnp.array_equal(scan[0], to_expert)
                                  & jnp.array_equal(scan[1], to_source)),
            "ms": {
                "index_arrays_scan": ms(jax.jit(scan_indices), cnt),
                "index_arrays_compare_all": ms(jax.jit(
                    lambda c: (plan_of(c).to_expert_major,
                               plan_of(c).to_source_major)), cnt),
                "gather_by_expert": ms(jax.jit(lambda g, i: g[i]), got,
                                          to_expert),
                "gather_by_source": ms(jax.jit(lambda g, i: g[i]), got,
                                          to_source),
                "composed_by_expert": ms(jax.jit(
                    lambda g, c: plan_of(c).by_expert(g)), got, cnt),
                "composed_by_source": ms(jax.jit(
                    lambda g, c: plan_of(c).by_source(g)), got, cnt),
                "take": ms(jax.jit(lambda x, o: x[o // k]), x, order),
                "zero_fill": ms(jax.jit(
                    lambda: jnp.zeros((budget, H), dtype))),
                "weights_composed": ms(jax.jit(
                    lambda w, c: plan_of(c).by_expert(w)), weights, cnt),
                "weights_as_lanes": ms(jax.jit(
                    lambda w, c: plan_of(c, interpret).by_expert(w)),
                    weights, cnt)}}
        want = {"by_expert": got[to_expert], "by_source": got[to_source]}
        # the ragged wire: the live rows lead the buffer in both orders
        ahead = jnp.arange(budget)[:, None] < live
        for rows_plan in ["default"] + list(args.rows_plans):
            if rows_plan != "default":
                block, sub = map(int, rows_plan.split("x"))
                kernel_rows.BLOCK_ROWS, kernel_rows.SUB_ROWS = block, sub
            key = f"kernel_{rows_plan}"
            try:
                for way in ("by_expert", "by_source"):
                    f = jax.jit(lambda g, c, way=way: getattr(
                        plan_of(c, interpret), way)(g))
                    out = f(got, cnt)
                    equal = bool(jnp.array_equal(
                        jnp.where(ahead, out, 0).view(jnp.uint16),
                        jnp.where(ahead, want[way], 0).view(jnp.uint16)))
                    zero = bool(jnp.all(jnp.where(ahead, 0, out) == 0))
                    here["ms"][f"{key}_{way}"] = ms(f, got, cnt)
                    here[f"{key}_{way}_bit_for_bit"] = equal and zero
            except Exception as e:      # noqa: BLE001  a plan Mosaic
                here[key] = f"{type(e).__name__}: {e}"[:400]    # refuses
            finally:    # the module's own rows a block and a piece again
                importlib.reload(kernel_rows)
        result[name] = here
        say(f"{name}: {here}")
    return result


def share(args) -> dict:
    """One chip's share without the exchange: the chip's 2 sequences, 16 of
    the 64 experts held (the router keeps its 64 outputs), a quarter of the
    vocabulary, no mesh."""
    cell = laguna_probe.load_cell(args)
    chips = cell["chips"]
    model, params = cell["model"], cell["params"]
    for key in ("expert_axis", "vocab_axis"):
        model.pop(key)
    model.update(num_experts_routed=model["num_experts"],
                 num_experts=model["num_experts"] // chips,
                 first_expert_held=0,
                 # a chip's tokens send a quarter of what four chips' do
                 moe_row_budget=model["moe_row_budget"] // chips,
                 vocab_size=model["vocab_size"] // chips)
    params["batch"] //= chips
    cell.update(chips=1, layout=None)
    import benchmark.jobs.common as common
    real = common.reference_check       # the uncut reference is the cell's
    common.reference_check = lambda s, batch: True
    try:
        got = steps_of(cell, args.seed, args.steps)
    finally:
        common.reference_check = real
    result = {"mode": "share", "seed": args.seed, **got,
              "lowerings": lowerings()}
    say(f"one chip's share without the exchange: {got['step_ms']:.2f} ms a "
        f"step, peak {got['peak_gb']:.3f} GB")
    return result


def options(ap):
    ap.set_defaults(cell=CELL, steps=20)
    ap.add_argument("--impls", nargs="*", default=["ragged", "padded"])
    ap.add_argument("--seeds", nargs="*", type=int,
                    default=[2147480261, 2147480297, 2147480333])
    ap.add_argument("--rows-plans", nargs="*", default=[],
                    help="passes: also time the kernel at these rows a "
                         "block x rows a piece, e.g. 512x128 2048x256")


if __name__ == "__main__":
    sys.exit(laguna_probe.main(
        modes={"load": load, "wire": wire, "controls": controls,
               "readings": readings, "share": share, "passes": passes},
        doc=__doc__,
        options=options))
