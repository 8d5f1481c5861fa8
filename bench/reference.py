"""Plain reference of a decoder-only MoE language model (Qwen3-MoE,
Mixtral), in jax.numpy and float32 at full matmul precision.

It follows the published model: pre-norm RMSNorm blocks, grouped-query
attention with rotate-half RoPE (and per-head RMSNorm of q and k where the
config says ``qk_norm``), a softmax router whose top-k weights are
renormalised, SwiGLU experts, a final RMSNorm and an untied head.  Every
token reaches each of its top-k experts (no capacity, nothing dropped).  It
imports nothing of the program: weights come from ``bench/weights.py`` and
the seed.

It runs layer by layer over a batch of whole sequences, so only one layer's
weights are on the device at a time.  ``control`` computes every weight
matmul in a lower precision instead (``int8``: both operands quantised per
row / per column with int32 accumulation; ``fp8``: both operands in
float8_e4m3 with per-row / per-column scales) and puts that model in the
program's place: the control that the correctness limit has to reject.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import weights as W

F32 = jnp.float32


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """x (B, S, H, hd), positions (S,): rotate-half RoPE."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]          # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _quant(x, axis, kind):
    """Symmetric quantisation of ``x`` along ``axis`` (the contracted one):
    returns (codes, scale) with x ~ codes * scale."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if kind == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn), scale


def matmul(x, w, control=None):
    """x (..., k) @ w (k, n) in f32, or in the control's precision."""
    w = w.astype(F32)
    if control is None:
        return x @ w
    xq, xs = _quant(x, -1, control)
    wq, ws = _quant(w, 0, control)
    if control == "int8":
        acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return acc.astype(F32) * xs * ws
    return (xq.astype(F32) @ wq.astype(F32)) * xs * ws


def _attention(q, k, v, n_rep):
    """Causal GQA for one sequence: q (S, H, hd), k/v (S, Hkv, hd)."""
    s, h, hd = q.shape
    k = jnp.repeat(k, n_rep, axis=1)
    v = jnp.repeat(v, n_rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def layer(h, w, arch: dict, control=None, prompt=None):
    """One decoder layer on h (B, S, d) float32.  Returns the new h and,
    per sequence, the most prompt tokens (``prompt`` (B, S) marks them)
    that any one expert received."""
    b, s, d = h.shape
    eps, hd = arch["rms_norm_eps"], arch["head_dim"]
    nh, nkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    pos = jnp.arange(s)
    x = rms_norm(h, w["ln1"], eps)
    q = matmul(x, w["wq"], control).reshape(b, s, nh, hd)
    k = matmul(x, w["wk"], control).reshape(b, s, nkv, hd)
    v = matmul(x, w["wv"], control).reshape(b, s, nkv, hd)
    if arch.get("qk_norm"):
        q = rms_norm(q, w["q_norm"], eps)
        k = rms_norm(k, w["k_norm"], eps)
    q = rope(q, pos, arch["rope_theta"])
    k = rope(k, pos, arch["rope_theta"])
    att = jax.lax.map(lambda qkv: _attention(*qkv, nh // nkv), (q, k, v))
    h = h + matmul(att.reshape(b, s, nh * hd), w["wo"], control)

    x = rms_norm(h, w["ln2"], eps).reshape(b * s, d)
    probs = jax.nn.softmax(matmul(x, w["router"], control), axis=-1)
    top, idx = jax.lax.top_k(probs, arch["num_experts_per_tok"])
    if arch.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(b * s)[:, None], idx].set(top)
    hit = (gate > 0).reshape(b, s, -1)
    if prompt is not None:
        hit = hit & prompt[..., None]
    load = jnp.max(jnp.sum(hit, axis=1), axis=-1)

    def expert(y, e):
        w1, w3, w2, g = e
        u = jax.nn.silu(matmul(x, w1, control)) * matmul(x, w3, control)
        return y + g[:, None] * matmul(u, w2, control), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (w["w1"], w["w3"], w["w2"], gate.T))
    return h + y.reshape(b, s, d), load


@partial(jax.jit, static_argnames=("arch_items", "control"))
def _layer_jit(h, key, l, prompt, arch_items, control):
    arch = dict(arch_items)
    return layer(h, W.layer_weights(key, arch, l), arch, control, prompt)


@partial(jax.jit, static_argnames=("arch_items",))
def _embed_jit(tokens, key, arch_items):
    hw = W.head_weights(key, dict(arch_items))
    return hw["embed"].astype(F32)[tokens]


@partial(jax.jit, static_argnames=("arch_items", "control"))
def _head_jit(h, rows, key, arch_items, control):
    """Logits (B, M, V) at positions ``rows`` (B, M) of h (B, S, d)."""
    arch = dict(arch_items)
    hw = W.head_weights(key, arch)
    hs = jnp.take_along_axis(h, rows[..., None], axis=1)
    hs = rms_norm(hs, hw["final_norm"], arch["rms_norm_eps"])
    return matmul(hs, hw["lm_head"], control)


def served_gaps(arch: dict, seed: int, tokens, rows, served, valid,
                prompt_len, control=None):
    """Gaps below the reference's best logit, token by token.

    ``tokens`` (B, S): each sampled request's prompt and served tokens,
    right-padded (causal attention never looks right, so padding is inert).
    ``rows`` (B, M): the position that predicts each served token,
    ``served`` (B, M) the token ids, ``valid`` (B, M) which entries are
    real, ``prompt_len`` (B,) each prompt's length.

    The gap of a token is ``max(ref) - ref[token]``.  Returns, for the
    served tokens: ``served.max`` the widest gap per request,
    ``served.mean`` the mean over every token, ``served.share`` the share
    of tokens whose gap is above 0 (the reference would have picked
    another); and ``load`` per request, the most prompt tokens one expert
    received in any layer, over the mean (n * top_k / E).

    With ``control`` (``"int8"`` or ``"fp8"``) the reference computed in
    that precision is put in the program's place: at each position of the
    same prompts and served tokens, the token its logits put first is the
    one served, and the ``served.*`` readings are of those tokens; the
    program's own tokens are read under ``program.*``.
    """
    key = W.root_key(seed)
    items = tuple(sorted(arch.items()))
    valid = jnp.asarray(valid)
    n = jnp.asarray(prompt_len)
    prompt = jnp.arange(tokens.shape[1])[None, :] < n[:, None]
    load = jnp.zeros(n.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = hc = _embed_jit(jnp.asarray(tokens), key, items)
        for l in range(arch["num_hidden_layers"]):
            h, ld = _layer_jit(h, key, l, prompt, items, None)
            load = jnp.maximum(load, ld)
            if control is not None:
                hc, _ = _layer_jit(hc, key, l, prompt, items, control)
        rows = jnp.asarray(rows)
        ref = _head_jit(h, rows, key, items, None)
        best = jnp.max(ref, axis=-1)
        nv = jnp.maximum(jnp.sum(valid), 1)

        def stats(name, tok):
            got = jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
            gap = jnp.where(valid, best - got, 0.0)
            return {f"{name}.max": jax.device_get(jnp.max(gap, axis=-1)),
                    f"{name}.mean": float(jnp.sum(gap) / nv),
                    f"{name}.share": float(jnp.sum(gap > 0) / nv)}
        served = jnp.asarray(served)
        out = {}
        if control is not None:
            out.update(stats("program", served))
            low = _head_jit(hc, rows, key, items, control)
            served = jnp.argmax(low, axis=-1).astype(served.dtype)
        out.update(stats("served", served))
    mean = n * arch["num_experts_per_tok"] / arch["num_experts"]
    out["load"] = jax.device_get(load / mean)
    return out
