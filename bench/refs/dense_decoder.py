"""Plain float32 reference of a dense decoder, from its description.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * norm1
                x += attention(h) @ wo       (GQA, causal, RoPE on q, k)
                h = rmsnorm(x) * norm2
                x += (silu(h @ w_gate) * (h @ w_up)) @ w_down
    logits = (rmsnorm(x) * final_norm) @ head

RoPE rotates adjacent pairs (2i, 2i+1) of the first ``rot`` dims of each
head by ``pos * theta**(-2i / rot)``: ``rot`` is the whole head for the
"full" style (LLaMA, Yi) and its first half for "half" (ChatGLM2/3,
whose other half passes through).  Attention scores are scaled by
``1/sqrt(head_dim)``.  Every matrix product runs at full float32
precision (``Precision.HIGHEST``), which a TPU does not use unless
asked.  Nothing here comes from the program under test.

``rnd`` rounds every tensor a layer produces and every operand of a
product; the identity gives the reference, a cast through a narrower
type gives the control that computes in that type.

The reference also owns its weights' layout (``bench.weights`` draws
them, ``bench/layouts/dense_decoder.json`` maps them onto the program's
tree) and the work of a decode step (``work``, read by ``bench.costs``):
per-layer arrays stacked on a leading ``n_layers`` axis, matrices
``[in, out]``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# the order fixes each leaf's fold_in index (bench.weights)
LEAVES = ("embed", "head", "final_norm", "norm1", "wq", "wk", "wv", "wo",
          "norm2", "w_gate", "w_up", "w_down")
NORMS = frozenset({"final_norm", "norm1", "norm2"})
F32_LEAVES = frozenset()        # every leaf is held in the param dtype


def shapes(dims: dict) -> dict:
    d, f, v, n = dims["d_model"], dims["d_ff"], dims["vocab"], \
        dims["n_layers"]
    hq, hkv, dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    return {
        "embed": (v, d), "head": (d, v), "final_norm": (d,),
        "norm1": (n, d), "wq": (n, d, hq * dh), "wk": (n, d, hkv * dh),
        "wv": (n, d, hkv * dh), "wo": (n, hq * dh, d), "norm2": (n, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
    }


def draw(name: str, key, shape: tuple):
    """One leaf's float32 values: norm scales U(0.75, 1.25); matrices
    N(0, 1/fan_in), embeddings N(0, 1/d_model)."""
    if name in NORMS:
        return jax.random.uniform(key, shape, jnp.float32, 0.75, 1.25)
    fan_in = shape[1] if name == "embed" else shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def work(dims: dict) -> dict:
    """What one decode step does per advanced row (``bench.costs``):
    the weights a token multiplies by (every layer's projections, the
    three SwiGLU matrices, the head), the norms called ([width, calls]:
    two a layer and the final one), the layers that hold K/V and call
    ``decode_attn``, the query width, and the K and V elements of one
    position in one such layer."""
    dm, dh, n = dims["d_model"], dims["head_dim"], dims["n_layers"]
    hq, hkv = dims["n_heads"], dims["n_kv_heads"]
    layer = dm * (hq + 2 * hkv) * dh + hq * dh * dm + 3 * dm * dims["d_ff"]
    return {"matmul_params": n * layer + dm * dims["vocab"],
            "norms": [[dm, 2 * n + 1]], "attn_layers": n,
            "attn_width": hq * dh, "kv_row": 2 * hkv * dh, "d_model": dm}


def _identity(x):
    return x


def _mm(a, b, rnd):
    return rnd(jnp.matmul(rnd(a), rnd(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32))


def _rmsnorm(x, w, eps, rnd):
    return rnd(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
               * w)


def _rope(x, pos, dims):
    """x [B, S, H, dh]; pos [S]."""
    dh = x.shape[-1]
    rot = dh if dims["rope_style"] == "full" else dh // 2
    inv = dims["rope_theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32)
                                 / rot)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, rot/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([y.reshape(x[..., :rot].shape), x[..., rot:]],
                           -1)


def _attention(h, w, dims, rnd):
    b, s, _ = h.shape
    hq, hkv, dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    pos = jnp.arange(s)
    q = _rope(_mm(h, w["wq"], rnd).reshape(b, s, hq, dh), pos, dims)
    k = _rope(_mm(h, w["wk"], rnd).reshape(b, s, hkv, dh), pos, dims)
    v = _mm(h, w["wv"], rnd).reshape(b, s, hkv, dh)
    q, k = rnd(q), rnd(k)
    q = q.reshape(b, s, hkv, hq // hkv, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HIGHEST,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(dh))
    causal = pos[None, :] <= pos[:, None]                  # [q, s]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = rnd(jax.nn.softmax(scores, axis=-1))
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    return _mm(rnd(o).reshape(b, s, hq * dh), w["wo"], rnd)


def logits(w: dict, tokens, dims: dict, rnd=_identity):
    """[B, S] token ids → [B, S, vocab] float32 logits."""
    eps = dims["norm_eps"]
    x = rnd(w["embed"].astype(jnp.float32)[tokens])
    layers = {k: w[k].astype(jnp.float32)
              for k in ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate",
                        "w_up", "w_down")}

    def layer(x, lw):
        h = _rmsnorm(x, lw["norm1"], eps, rnd)
        x = rnd(x + _attention(h, lw, dims, rnd))
        h = _rmsnorm(x, lw["norm2"], eps, rnd)
        g = _mm(h, lw["w_gate"], rnd)
        u = _mm(h, lw["w_up"], rnd)
        x = rnd(x + _mm(rnd(jax.nn.silu(g) * u), lw["w_down"], rnd))
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    x = _rmsnorm(x, w["final_norm"].astype(jnp.float32), eps, rnd)
    return _mm(x, w["head"].astype(jnp.float32), rnd)
