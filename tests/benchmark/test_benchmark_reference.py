"""The reference check of the benchmark (jobs/common.py:reference_check) at
small sizes on the CPU: the program agrees with the plain float32 reference
inside the tolerance written in the reference file, and the same check
rejects a program run at a precision below the configuration's."""
import numpy as np
import pytest

from benchmark import run
from benchmark.jobs import common


def session(cell_name):
    cell = run.load_cell(cell_name, rehearsal=True)
    said = []
    s = common.Session(cell, 5, said.append)
    batch = s.builder.batch(s.model, s.params, np.random.RandomState(5))
    return s, batch, said


@pytest.mark.parametrize("cell", ["bert_base.pretrain_s128",
                                  "deepfm_criteo.files_b4096"])
def test_program_agrees_with_the_plain_reference(cell):
    s, batch, said = session(cell)
    try:
        assert common.reference_check(s, batch) is True
        assert "ok" in said[-1] and "FAILED" not in said[-1]
    finally:
        s.close()


@pytest.mark.parametrize("cell", ["bert_base.pretrain_s128",
                                  "deepfm_criteo.files_b4096"])
def test_a_lower_precision_fails_the_same_check(cell):
    """Weights rounded to float8 (e4m3: 3 bits of mantissa against
    bfloat16's 7 and float32's 23) while the reference keeps the originals:
    the comparison that decides ``correct`` must say no."""
    import jax.numpy as jnp
    s, batch, said = session(cell)
    try:
        originals = {n: s.scope.find_var(n) for n in s.built["params"]}
        for n, v in originals.items():
            s.scope.set_var(n, jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(v.dtype))
        ref_mod = __import__(f"benchmark.references.{s.cell['reference']}",
                             fromlist=["loss"])
        real_loss = ref_mod.loss
        ref_mod.loss = lambda w, *a: real_loss(
            [originals[n] for n in s.built["params"]], *a)
        try:
            assert common.reference_check(s, batch) is False
        finally:
            ref_mod.loss = real_loss
        assert "FAILED" in said[-1]
    finally:
        s.close()
