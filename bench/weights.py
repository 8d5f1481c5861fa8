"""Seeded model weights, made on the device, the same for the program and
the reference.

Every weight is a stack of 2-D (or 1-D) slices, and each slice is drawn
from its own key: the root key of ``--seed``, folded with the slice's name
and its canonical index (layer, expert).  So the program's whole parameter
tree can be built in one jitted call, and the reference can make any single
layer again, bit for bit, without taking anything the program made.

Values are ``k * scale`` with ``k`` an integer in [-32768, 32767] read from
the random bits: one f32 multiply and one cast, so no backend can round
them differently.

The projections that write into the residual stream (attention ``wo``, the
experts' ``w2``) are scaled by ``1 / sqrt(2 * layers)``, the residual
scaling of GPT-2 and Megatron-LM's ``scaled_init_method``.  Without it the
random stream collapses toward one direction within a few layers (deep
random attention loses rank) and the router sends most tokens of a prompt
to a few experts, up to 13.9 times the mean load in the last of 8 layers
(measured on one v5e chip), which no trained, load-balanced router does; with it
the heaviest expert stays near 1.5 times the mean.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
RESIDUAL = ("wo", "w2")


def residual_scale(layers: int) -> float:
    return 1.0 / math.sqrt(2.0 * layers)


def root_key(seed: int) -> jax.Array:
    """A threefry key from any whole number below 2**64."""
    s = int(seed) % (1 << 64)
    data = jnp.array([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def _slice_key(key, name: str, idx) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    for i in idx:
        k = jax.random.fold_in(k, i)
    return k


def slice_values(key, name: str, idx, shape, dtype=jnp.bfloat16,
                 resid: float = 1.0):
    """One slice of weight ``name`` at canonical index ``idx``.

    Norm scales lie in [0.8, 1.2]; the embedding has unit variance; every
    projection (``fan_in = shape[-2]``) keeps the variance of its input,
    times ``resid`` for those that write into the residual stream."""
    bits = jax.random.bits(_slice_key(key, name, idx), tuple(shape),
                           jnp.uint32)
    k = (bits >> 16).astype(jnp.int32) - 32768          # [-32768, 32767]
    leaf = name.rsplit("/", 1)[-1]
    if leaf in NORMS:
        return (1.0 + k.astype(jnp.float32) * (0.2 / 32768)).astype(dtype)
    if leaf == "embed":
        half = math.sqrt(3.0)
    else:
        half = math.sqrt(3.0 / shape[-2])
        if leaf in RESIDUAL:
            half *= resid
    return (k.astype(jnp.float32) * (half / 32768)).astype(dtype)


def _lead_dims(name: str, ndim: int) -> int:
    """How many leading axes of a program leaf index slices."""
    leaf = name.rsplit("/", 1)[-1]
    if not name.startswith("layers/"):
        return 0
    return ndim - (1 if leaf in NORMS else 2)


def _canonical(name: str, idx, shape):
    """Program index -> canonical index.  Expert leaves are laid out
    (layer, lane, local expert, ...); the canonical index is (layer, expert)
    with experts held contiguously by lane."""
    if len(idx) == 3:
        l, lane, j = idx
        return (l, lane * shape[2] + j)
    return tuple(idx)


def build_leaf(key, name: str, shape, dtype, resid: float):
    lead = _lead_dims(name, len(shape))
    inner = shape[lead:]

    def at(*idx):
        return slice_values(key, name, _canonical(name, idx, shape), inner,
                            dtype, resid)

    def nest(prefix, dim):
        if dim == lead:
            return at(*prefix)
        return jax.lax.map(lambda i: nest(prefix + (i,), dim + 1),
                           jnp.arange(shape[dim], dtype=jnp.int32))
    return nest((), 0)


def path_name(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in kp)


def build_tree(key, shapes, layers: int):
    """Every leaf of a ShapeDtypeStruct tree, from ``key``, for a model of
    ``layers`` decoder layers."""
    resid = residual_scale(layers)
    return jax.tree_util.tree_map_with_path(
        lambda kp, s: build_leaf(key, path_name(kp), s.shape, s.dtype, resid),
        shapes)


def layer_weights(key, arch: dict, layer: int, dtype=jnp.bfloat16) -> dict:
    """Canonical weights of one decoder layer, for the reference:
    attention projections (d, out), norms, router (d, E) and experts
    w1/w3 (E, d, f), w2 (E, f, d)."""
    d, hd = arch["hidden_size"], arch["head_dim"]
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    e, f = arch["num_experts"], arch["moe_intermediate_size"]
    resid = residual_scale(arch["num_hidden_layers"])
    g = lambda n, shape: slice_values(key, n, (layer,), shape, dtype, resid)
    w = {
        "ln1": g("layers/ln1", (d,)), "ln2": g("layers/ln2", (d,)),
        "wq": g("layers/attn/wq", (d, h * hd)),
        "wk": g("layers/attn/wk", (d, kv * hd)),
        "wv": g("layers/attn/wv", (d, kv * hd)),
        "wo": g("layers/attn/wo", (h * hd, d)),
        "router": g("layers/moe/router", (d, e)),
    }
    if arch.get("qk_norm"):
        w["q_norm"] = g("layers/attn/q_norm", (hd,))
        w["k_norm"] = g("layers/attn/k_norm", (hd,))
    experts = jnp.arange(e, dtype=jnp.int32)
    for n, shape in (("w1", (d, f)), ("w3", (d, f)), ("w2", (f, d))):
        w[n] = jax.lax.map(
            lambda i: slice_values(key, f"layers/moe/{n}", (layer, i), shape,
                                   dtype, resid), experts)
    return w


def head_weights(key, arch: dict, dtype=jnp.bfloat16) -> dict:
    d, v = arch["hidden_size"], arch["vocab_size"]
    return {"embed": slice_values(key, "embed", (), (v, d), dtype),
            "final_norm": slice_values(key, "final_norm", (), (d,), dtype),
            "lm_head": slice_values(key, "lm_head", (), (d, v), dtype)}
