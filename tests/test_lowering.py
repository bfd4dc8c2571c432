"""Distribution/lowering infrastructure tests.

The production 256/512-device meshes need the dry-run entrypoint (subprocess
with XLA_FLAGS); here a subprocess with 8 host devices lowers + compiles a
representative subset of cells on a (2,2,2) pod/data/model mesh — the same
code path as the full dry-run, small enough for CI.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.launch.mesh import _make_mesh
    from repro.launch.steps import build_plan

    mesh = _make_mesh((2, 2, 2), ("pod", "data", "model"), jax.devices()[:8])
    out = []
    for arch, shape in json.loads(sys.argv[1]):
        plan = build_plan(arch, shape, reduced=True, multi_pod=True)
        if plan.skip:
            out.append([arch, shape, "skip"])
            continue
        compiled = plan.lower(mesh).compile()
        ca = compiled.cost_analysis() or {}
        out.append([arch, shape, "ok", float(ca.get("flops", 0))])
    print("RESULT " + json.dumps(out))
""")

CELLS = [
    ["qwen1.5-0.5b", "train_4k"],
    ["gemma2-2b", "long_500k"],
    ["qwen2-moe-a2.7b", "decode_32k"],
    ["mace", "molecule"],
    ["dlrm-rm2", "train_batch"],
    ["xdeepfm", "retrieval_cand"],
    ["granite-8b", "long_500k"],  # mandated skip
]


@pytest.mark.slow
def test_reduced_cells_compile_on_multipod_mesh():
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT, json.dumps(CELLS)],
        capture_output=True, text=True, env=env, timeout=1500,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    results = json.loads(line[len("RESULT "):])
    status = {(a, s): st for a, s, st, *rest in results}
    assert status[("granite-8b", "long_500k")] == "skip"
    for (a, s), st in status.items():
        if (a, s) != ("granite-8b", "long_500k"):
            assert st == "ok", (a, s)


def test_collective_parser():
    from repro.launch.dryrun import parse_collectives

    hlo = """
    %ag = bf16[16,128]{1,0} all-gather(%x), replica_groups={}
    %ar.1 = f32[256]{0} all-reduce(%y), to_apply=%sum
    %cp = f32[8,8]{1,0} collective-permute(%z)
    ROOT %t = (f32[4]{0}) tuple(%ar.1)
    %ag2s = bf16[64]{0} all-gather-start(%w)
    %ag2d = bf16[64]{0} all-gather-done(%ag2s)
    """
    out = parse_collectives(hlo)
    assert out["all-gather"]["count"] == 2  # ag + ag-start (done not counted)
    assert out["all-gather"]["bytes"] == 16 * 128 * 2 + 64 * 2
    assert out["all-reduce"]["bytes"] == 256 * 4
    assert out["collective-permute"]["count"] == 1
    assert out["total_count"] == 4


def test_variant_parsing():
    from repro.launch.dryrun import _parse_variant

    v = _parse_variant("unroll_layers=True,n_microbatches=4,remat_policy=dots")
    assert v == {"unroll_layers": True, "n_microbatches": 4,
                 "remat_policy": "dots"}


def test_param_spec_rules_cover_all_leaves():
    import jax

    from repro import configs as C
    from repro.distributed import sharding as sl
    from repro.models import transformer as tfm

    for arch in ["qwen2-moe-a2.7b", "gemma2-2b"]:
        cfg = C.get_arch(arch).make_reduced()
        shapes = jax.eval_shape(
            lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
        specs = sl.lm_param_specs(shapes)
        # every leaf got a spec whose rank fits the leaf
        flat_s = jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "_normalized_spec_for_aval")
            or x.__class__.__name__ == "PartitionSpec")
        flat_l = jax.tree.leaves(shapes)
        assert len(flat_s) == len(flat_l)
        for sp, leaf in zip(flat_s, flat_l):
            assert len(sp) <= leaf.ndim or len(sp) == 0
