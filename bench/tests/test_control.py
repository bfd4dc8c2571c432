"""The comparison's control: the plain reference, put in the program's
place one precision step lower (three bf16 passes), must come out not
correct; the reference at full precision must come out correct."""
import numpy as np
import pytest

from bench import checks, control, spec
from bench.tests import tiny


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
def test_control_is_not_correct(name):
    cell = tiny.cell(name)
    out = control.readings(cell, 7, 2.0, per_client=4)
    assert not out["correct"]
    gap = out["checks"]["dist_gap"]
    assert gap["value"] > gap["limit"]


def test_reference_in_full_precision_is_correct():
    cell = tiny.cell("deep1b-flat.offline")
    cfg = cell.config
    import jax

    from bench.run import seed_key

    gen = spec.module("generators", cfg["data"]["generator"])
    key = jax.random.fold_in(seed_key(cfg["data_seed"]), 0)
    corpus, pool = gen.make(key, rows=cfg["rows"], pool=cell.traffic["pool"],
                            dim=cfg["dim"], intrinsic=12, noise=0.01)
    pool = np.asarray(pool)
    ref = spec.module("references", cfg["reference"])
    rows = control.request_rows(cell, 7, 2.0, per_client=4)
    d2, ids = ref.search(pool[rows], corpus, 10)
    numbers = checks.readings(ref, pool, corpus, rows,
                              np.sqrt(np.maximum(d2, 0)), ids)
    assert checks.judge(numbers, cfg["correct"], [])[0], numbers
