"""Launch-layer integration: build_case lowers/compiles on a 1-device mesh
with reduced configs (the production-mesh version is the dry-run, run as
its own 512-device process)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, ShapeConfig
from repro.configs.registry import get_arch
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib
from repro.utils import hlo_cost


@pytest.fixture(scope="module")
def tiny_mesh():
    return mesh_lib.make_host_mesh(1, 1)


def _tiny_case(arch, shape_name, mesh):
    cfg = get_arch(arch, shape_name).reduced()
    shp = SHAPES[shape_name]
    small = ShapeConfig(shp.name, seq_len=64, global_batch=2, kind=shp.kind)
    import repro.configs.registry as reg
    orig_arch, orig_shape = reg.get_arch, specs_lib.get_shape
    try:
        specs_lib.get_arch = lambda a, s=None: cfg
        specs_lib.get_shape = lambda s: small
        case = specs_lib.build_case(arch, shape_name, mesh,
                                    overrides=dict(param_dtype="float32",
                                                   compute_dtype="float32"))
    finally:
        specs_lib.get_arch, specs_lib.get_shape = orig_arch, orig_shape
    return case


@pytest.mark.parametrize("arch,shape", [
    ("olmo-1b", "train_4k"),
    ("gemma-2b", "decode_32k"),
    ("deepseek-moe-16b", "train_4k"),
    ("zamba2-7b", "decode_32k"),
    ("whisper-medium", "prefill_32k"),
])
def test_case_lowers_and_runs(arch, shape, tiny_mesh):
    case = _tiny_case(arch, shape, tiny_mesh)
    with tiny_mesh:
        compiled = case.jit().lower(*case.args).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    assert cost.flops > 0
    assert cost.bytes > 0
    # executable for real with concrete zeros/randoms
    kk = [jax.random.PRNGKey(3)]
    def concretize(s):
        kk[0] = jax.random.fold_in(kk[0], 1)
        if jnp.issubdtype(s.dtype, jnp.integer):
            # tokens/indices: small nonzero values (all-zero tokens make
            # norm backward degenerate)
            return jnp.abs(jax.random.randint(kk[0], s.shape, 0, 7)).astype(s.dtype)
        return jax.random.normal(kk[0], s.shape, jnp.float32).astype(s.dtype) * 0.02
    args = jax.tree_util.tree_map(concretize, case.args)
    out = compiled(*args)
    leaves = jax.tree_util.tree_leaves(out)
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32)))) for l in leaves
               if jnp.issubdtype(l.dtype, jnp.floating))


def test_mesh_helpers():
    m = mesh_lib.make_host_mesh(1, 1)
    assert mesh_lib.n_workers(m) == 1
    assert mesh_lib.model_size(m) == 1
    assert mesh_lib.worker_axes(False) == ("data",)
    assert mesh_lib.worker_axes(True) == ("pod", "data")
    # every axis Auto: slicing a sharded buffer is then no sharding error
    assert set(m.axis_types) == {jax.sharding.AxisType.Auto}


@pytest.mark.parametrize("env_dir", [None, "placed"])
def test_compile_cache_placement(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is honoured and nothing else is
    set; otherwise the cache is the checkout's fixed .jax_cache."""
    import pathlib
    from repro.launch import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        got = compile_cache.setup_compile_cache()
        if env_dir is None:
            root = pathlib.Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
