"""Sharding rules: parameter/activation PartitionSpecs for the production mesh.

Logical layout (DESIGN.md §4):
  * DP   over ``data`` (+ ``pod`` for non-MoE archs / non-EP tensors)
  * TP   over ``model`` (attention heads, FFN columns, vocab)
  * EP   over ``model`` (single-pod) or (``pod``, ``model``) (multi-pod)
  * SP   sequence dim of activations over ``model`` between blocks
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def param_specs(params, *, multi_pod: bool, model_size: int = 16,
                fsdp_experts: bool = False) -> dict:
    """PartitionSpec pytree matching the model parameter pytree, by leaf path."""
    ep = ("pod", "model") if multi_pod else ("model",)

    def spec_for(path: str, leaf) -> P:
        nd = leaf.ndim

        # stacked-over-layers leaves get a leading L dim -> prepend None;
        # axes whose dim is not divisible by the mesh axis fall back to
        # replicated (explicit in_shardings require divisibility).
        def lay(*axes):
            dims = (None,) * (nd - len(axes)) + axes
            fixed = []
            for size, ax in zip(leaf.shape, dims):
                if ax == "model" and size % model_size != 0:
                    ax = None
                fixed.append(ax)
            return P(*fixed)
        if "embed" in path:
            if leaf.shape[0] % model_size == 0:
                return lay("model", None)        # (V, d) vocab-sharded
            return lay(None, "model")            # odd vocab: shard d
        if "lm_head" in path:
            if leaf.shape[-1] % model_size == 0:
                return lay(None, "model")        # (d, V)
            return lay("model", None)            # odd vocab: row-sharded
        if path.endswith(("wq", "wk", "wv")) or "in_proj_zx" in path:
            return lay(None, "model")            # columns = heads/inner
        if path.endswith(("wo", "out_proj")):
            return lay("model", None)
        if path.endswith(("w_gate", "w_up")):
            return lay(None, "model")
        if path.endswith("w_down"):
            return lay("model", None)
        if "moe" in path and path.endswith(("w1", "w3")):
            # lane-major expert weights (L, EP_lanes, E_local, d, f)
            if fsdp_experts:
                return lay(ep, None, None, "data")
            return lay(ep, None, None, None) if nd >= 4 else lay(ep, None, None)
        if "moe" in path and path.endswith("w2"):
            if fsdp_experts:
                return lay(ep, None, "data", None)
            return lay(ep, None, None, None) if nd >= 4 else lay(ep, None, None)
        if "moe" in path and "router" in path:
            return lay(None, None)
        if "conv_w" in path:
            return lay(None, "model")            # (K, conv_dim)
        # norms, per-head scalars (a_log/dt_bias/d_skip), biases: replicated
        return P(*([None] * nd))

    def path_str(kp):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)

    return jax.tree_util.tree_map_with_path(
        lambda kp, v: spec_for(path_str(kp), v), params)


def shardings(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def init_params_sharded(init, key, mesh, *, fsdp_experts: bool = False):
    """Run ``init(key)`` under ``jit`` with ``out_shardings`` from
    :func:`param_specs`, so every leaf is built in place on its own shard
    (no full copy on the default device, no f32 transient outside the
    fused init)."""
    shapes = jax.eval_shape(init, key)
    specs = param_specs(shapes, multi_pod="pod" in mesh.shape,
                        model_size=mesh.shape["model"],
                        fsdp_experts=fsdp_experts)
    return jax.jit(init, out_shardings=shardings(specs, mesh))(key)


def act_spec(multi_pod: bool, family: str) -> P:
    """Activation (B, S, d) spec between blocks: DP batch + SP sequence."""
    if multi_pod and family == "moe":
        return P(("data",), ("pod", "model"), None)
    if multi_pod:
        return P(("pod", "data"), ("model",), None)
    return P(("data",), ("model",), None)


def batch_spec(multi_pod: bool, family: str) -> P:
    """(B, S) token/label spec."""
    if multi_pod and family == "moe":
        return P(("data",), ("pod", "model"))
    if multi_pod:
        return P(("pod", "data"), ("model",))
    return P(("data",), ("model",))
