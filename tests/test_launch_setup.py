"""Launcher set-up shared by serving, training and the chip smoke: the
compile-cache location and sharded parameter initialisation."""

import pytest

from conftest import run_devices


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory;
    unset, the cache sits at the fixed <repo>/.jax_cache."""
    setenv = (f"os.environ['JAX_COMPILATION_CACHE_DIR'] = {str(tmp_path)!r}"
              if env_dir else
              "os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)")
    code = f"""
import os
{setenv}
import jax, jax.numpy as jnp
from repro.launch import compile_cache
path = compile_cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(17.0)).block_until_ready()
print("PATH", path)
print("CONFIG", jax.config.jax_compilation_cache_dir)
"""
    out = run_devices(code, 1)
    path = out.split("PATH ")[1].split("\n")[0]
    config = out.split("CONFIG ")[1].split("\n")[0]
    if env_dir:
        assert path == config == str(tmp_path)
        assert any(tmp_path.iterdir()), "no cache entry written"
    else:
        from repro.launch.compile_cache import REPO_CACHE
        assert path == config == str(REPO_CACHE)
        assert REPO_CACHE.name == ".jax_cache"
        assert (REPO_CACHE.parent / "src" / "repro").is_dir()
        assert not any(tmp_path.iterdir())


def test_init_params_sharded_places_leaves_by_spec(multidevice):
    """Parameters are built under jit straight into their param_specs
    shardings (experts over the model axis), not on device 0."""
    code = """
import jax
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh
from repro.configs import get_arch
from repro.models import zoo
from repro.models.lm import make_context
from repro.parallel import sharding as sh
cfg = get_arch("qwen3-moe-30b-a3b").reduced()
mesh = make_mesh((1, 4), ("data", "model"))
ctx = make_context(cfg, mesh, multi_pod=False, engine="fused_flat")
bundle = zoo.build(cfg, ctx)
params = sh.init_params_sharded(bundle.init, jax.random.PRNGKey(0), mesh)
specs = sh.param_specs(params, multi_pod=False, model_size=4)
flat = jax.tree.leaves(jax.tree.map(lambda x, s: x.sharding.spec == s,
                                    params, specs,
                                    is_leaf=lambda x: isinstance(x, P)))
assert all(flat), flat
w1 = params["layers"]["moe"]["w1"]
assert len({s.device for s in w1.addressable_shards}) == 4
assert w1.addressable_shards[0].data.shape[1] == w1.shape[1] // 4
# the same values as an unsharded jitted init: placement changes nothing
plain = jax.jit(bundle.init)(jax.random.PRNGKey(0))
same = jax.tree.map(lambda a, b: bool((a == b).all()), params, plain)
assert all(jax.tree.leaves(same))
print("SHARDED_INIT_OK")
"""
    assert "SHARDED_INIT_OK" in multidevice(code, 4)


def test_serve_layers_cuts_depth_only():
    """``--layers N`` keeps every width and cuts only the depth, says so,
    and the continuous launcher returns every request's tokens."""
    code = """
from repro.launch import serve
done = serve.main(["--arch", "qwen3-moe-30b-a3b", "--reduced", "--layers",
                   "1", "--continuous", "--requests", "2", "--prompt-len",
                   "8", "--gen", "3"])
assert sorted(len(r.output) for r in done) == [3, 3]
print("SERVE_OK")
"""
    out = run_devices(code, 1)
    assert "depth cut: qwen3-moe-30b-a3b 2 -> 1 layers" in out
    assert "SERVE_OK" in out
