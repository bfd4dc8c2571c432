"""chip_smoke.py rehearsed on CPU: its phases at a tiny size with the Pallas
kernels in interpret mode and the same parity and recall checks, the 4-way
sharded phase on four virtual CPU devices, its refusal to run without a TPU,
and the compile-cache helper it shares with the serving CLI."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

N, BATCH, BATCHES = 3000, 16, 2


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", autouse=True)
def _x32():
    """chip_smoke runs at JAX's default f32; some test modules turn x64 on
    globally when they are imported."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", prev)


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def data(smoke):
    corpus, queries = smoke.make_data(0, N, BATCH * BATCHES, smoke.DIM)
    truth = np.asarray(smoke.exact_topk(queries, corpus, smoke.NEIGHBORS))
    return corpus, queries, truth


def test_exact_reference_matches_numpy(smoke, data):
    corpus, queries, truth = data
    c, q = np.asarray(corpus, np.float64), np.asarray(queries, np.float64)
    d2 = ((q[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1, kind="stable")[:, :smoke.NEIGHBORS]
    assert smoke.recall(truth, want) == 1.0


@pytest.mark.parametrize("name,build_kw", [
    ("ivf_f32", dict(index="ivf", storage="float32")),
    ("ivf_int8", dict(index="ivf", storage="int8")),
    ("flat", dict(index="flat")),
])
def test_serving_phase_passes_its_checks(smoke, data, name, build_kw):
    corpus, queries, truth = data
    r = smoke.run_serving_phase(
        name, corpus, queries, truth, key=jax.random.PRNGKey(0),
        build_kw=build_kw, batch=BATCH, interpret=True)
    assert r["parity"]["ok"], r["parity"]
    assert r["parity"]["slots"] == BATCH * 64
    assert r["recall"] >= smoke.RECALL_FLOOR.get(name, 0.0), r["recall"]
    assert len(r["steady_query_ms"]) == BATCHES - 1


def test_stage_phase_round_trips_in_interpret_mode(smoke):
    r = smoke.run_stage_phase(0, n_tiles=3, force_kernel=True)
    assert r["ok"], r


def test_parity_allows_only_ties(smoke):
    d = np.array([[1.0, 2.0, 2.0, 3.0]], np.float32)
    ids = np.array([[7, 8, 9, 10]], np.int32)
    assert smoke.parity((d, ids), (d, ids))["ok"]
    swapped = np.array([[7, 9, 8, 10]], np.int32)  # tie at 2.0 reordered
    r = smoke.parity((d, swapped), (d, ids))
    assert r["ok"] and r["ids_differing"] == 2
    wrong = np.array([[7, 8, 11, 10]], np.int32)  # 11 at 2.0: no such tie
    assert not smoke.parity((d, wrong), (d, ids))["ok"]
    tail_tie = np.array([[1.0, 2.0, 2.0, 2.0]], np.float32)
    other = np.array([[7, 8, 9, 12]], np.int32)  # 12 ties the last at 2.0
    assert smoke.parity((tail_tie, other), (tail_tie, ids))["ok"]
    assert not smoke.parity((d + 1e-2, ids), (d, ids))["ok"]


def test_sharded_phase_matches_one_device_on_four_cpu_devices():
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import importlib.util, json, jax
        spec = importlib.util.spec_from_file_location("s", {SCRIPT!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        corpus, queries = s.make_data(0, {N}, {BATCH * BATCHES}, s.DIM)
        r = s.run_sharded_phase(corpus, queries, key=jax.random.PRNGKey(0),
                                devices=jax.devices(), batch={BATCH},
                                interpret=True)
        print("RESULT " + json.dumps(r))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    r = json.loads(line[0][len("RESULT "):])
    assert r["tile_devices"] == 4
    assert r["served_ids_equal"] and r["candidate_ids_equal"], r


def test_entry_point_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_compile_cache_follows_env_else_fixed_checkout_path(monkeypatch):
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev  # untouched

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
