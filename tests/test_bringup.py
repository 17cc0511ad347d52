"""Program plumbing that decides where jax runs: the persistent compile
cache's directory, the host mesh's model-parallel degree, and the
process-executor workers that must stay off the accelerator."""
import multiprocessing
from concurrent.futures import ProcessPoolExecutor

import jax
import pytest

from repro import compile_cache
from repro.dse.engine import _worker_init
from repro.launch.mesh import make_host_mesh


def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before    # set nothing


def test_compile_cache_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = compile_cache.CHECKOUT_DIR.parent
    assert (root / "src" / "repro" / "compile_cache.py").exists()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("model_parallel", [0, len(jax.devices()) + 1])
def test_host_mesh_rejects_non_dividing_model_parallel(model_parallel):
    with pytest.raises(ValueError, match="does not divide"):
        make_host_mesh(model_parallel)


def test_host_mesh_shape():
    n = len(jax.devices())
    assert dict(make_host_mesh(n).shape) == {"data": 1, "model": n}
    assert dict(make_host_mesh(1).shape) == {"data": n, "model": 1}


def _worker_platform():
    import os
    return jax.config.jax_platforms, os.environ["JAX_PLATFORMS"], \
        jax.default_backend()


def test_process_workers_stay_on_cpu():
    """Spawned DSE workers pin jax to the CPU before their first task."""
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=1, mp_context=ctx,
                             initializer=_worker_init) as pool:
        assert pool.submit(_worker_platform).result(timeout=120) == \
            ("cpu", "cpu", "cpu")
