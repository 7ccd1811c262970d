"""Multi-rank demonstration of the PT041 deadlock class: a collective
inside control flow whose branch differs across ranks.

Launched by test_analysis_distributed.py as 2 processes (the
test_multihost.py harness pattern). Each process:

1. builds the IR program the static analyzer flags (``build_ir_program``:
   a ``c_allreduce_sum`` inside a ``conditional_block`` -- the test
   asserts PT041 fires on exactly this IR);
2. executes the lowering that IR pair produces under a bound mesh axis --
   ``lax.cond`` selecting a ``psum`` branch inside ``shard_map`` -- with a
   RANK-DEPENDENT predicate ("divergent" mode, the default): half the mesh
   enters the psum, the other half never does, so the collective's
   rendezvous can never complete -> the process hangs (the parent kills it
   after a timeout) or the runtime errors. Either outcome is the
   demonstrated failure.

Pass "uniform" as argv[4] for the control run: the same program with a
rank-INDEPENDENT predicate completes and prints COMPLETED, proving the
harness itself is sound.
"""
import os
import sys


def build_ir_program():
    """The IR the verifier flags: psum under a divergent cond branch."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.framework import Program
    p = Program()
    gb = p.global_block()
    gb.create_var("x", (8, 4), "float32", is_data=True)
    gb.create_var("cond", (1,), "bool", is_data=True)
    sub = p._create_block()
    sub.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                  outputs={"Out": ["red"]}, attrs={"axis_name": "dp"},
                  infer_shape=False)
    p._rollback()
    gb.append_op("conditional_block",
                 inputs={"Cond": ["cond"], "X": ["x"]},
                 outputs={"Out": ["out"]},
                 attrs={"sub_block": sub.idx, "x_names": ["x"],
                        "out_names": ["red"]}, infer_shape=False)
    return p


def main():
    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    uniform = len(sys.argv) > 4 and sys.argv[4] == "uniform"

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np
    from paddle_tpu.parallel import env as penv

    if nproc > 1:
        penv.init_parallel_env(coordinator_address=f"127.0.0.1:{port}",
                               num_processes=nproc, process_id=rank)

    # the analyzer flags the IR this run demonstrates
    from paddle_tpu import analysis
    diags = analysis.verify(build_ir_program())
    flagged = any(d.code == "PT041" for d in diags)
    print(f"PT041_FLAGGED:{flagged}", flush=True)

    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("dp",))

    def per_device(x):
        idx = jax.lax.axis_index("dp")
        if uniform:
            pred = jnp.array(True)          # every rank takes the branch
        else:
            pred = idx < (len(devices) // 2)  # half the mesh diverges
        return jax.lax.cond(
            pred,
            lambda v: jax.lax.psum(v, "dp"),
            lambda v: v,
            x)

    fn = shard_map(per_device, mesh=mesh, in_specs=P("dp"),
                   out_specs=P("dp"), check_vma=False)

    x = jnp.arange(len(devices) * 4, dtype=jnp.float32).reshape(-1, 4)
    out = jax.jit(fn)(x)
    out.block_until_ready()   # the divergent run never returns from here
    print("COMPLETED:" + str(float(jnp.sum(out))), flush=True)


if __name__ == "__main__":
    main()
