"""DeepFM CTR with a HOST-RESIDENT embedding table (the parameter-server
analog): the big table never touches device HBM; rows are pulled per batch
and sparse grads pushed back with a server-side Adagrad.

Runs on whatever platform JAX selects (``JAX_PLATFORMS=cpu`` for a CPU
run); the single-table pull/push is hoisted out of the compiled step, so
no host callback is involved."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from a checkout without install

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import host_table


VOCAB, FIELDS, DIM = 20_000, 26, 16


def main():
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        ids = fluid.data("ids", [FIELDS], "int64")
        dense = fluid.data("dense", [13], "float32")
        label = fluid.data("label", [1], "float32")
        emb = layers.host_embedding(ids, (VOCAB, DIM), name="ctr_table",
                                    optimizer="adagrad", learning_rate=0.05)
        deep = layers.concat(
            [layers.reshape(emb, [-1, FIELDS * DIM]), dense], axis=1)
        for width in (256, 128):
            deep = layers.fc(deep, width, act="relu")
        logit = layers.fc(deep, 1)
        loss = layers.mean(
            layers.sigmoid_cross_entropy_with_logits(logit, label))
        fluid.optimizer.Adam(1e-3).minimize(loss)

    rng = np.random.RandomState(0)
    w_true = rng.randn(VOCAB).astype("float32") * 0.1
    exe = fluid.Executor()
    exe.run(startup)
    for step in range(200):
        b_ids = rng.randint(0, VOCAB, (512, FIELDS)).astype("int64")
        b_dense = rng.rand(512, 13).astype("float32")
        p = 1 / (1 + np.exp(-w_true[b_ids].sum(1)))
        b_y = (rng.rand(512) < p).astype("float32")[:, None]
        lv, = exe.run(main_p, feed={"ids": b_ids, "dense": b_dense,
                                    "label": b_y}, fetch_list=[loss])
        if step % 50 == 0:
            print(f"step {step}: loss {float(np.asarray(lv).reshape(())):.4f}"
                  f" (host-table pushes: "
                  f"{host_table.get_table('ctr_table').push_count})")


if __name__ == "__main__":
    main()
