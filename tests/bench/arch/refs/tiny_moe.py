"""Plain float32 reference of a decoder whose every FFN is a mixture of
experts, from its description (a test configuration: it lives only
with the tests, as a new architecture's files would in ``bench/``).

    per layer:  h = rmsnorm(x) * norm1
                x += attention(h) @ wo       (as bench/refs/dense_decoder)
                h = rmsnorm(x) * norm2
                p = softmax(h @ router);  top_k experts, gates p / sum(p)
                x += sum over the top_k of gate * expert(h)
    expert e:   (silu(h @ we_gate[e]) * (h @ we_up[e])) @ we_down[e]
    logits = (rmsnorm(x) * final_norm) @ head

Every expert is computed on every token and weighted by its gate
(zero outside the top_k), so no token is dropped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.refs import dense_decoder as dense

LEAVES = ("embed", "head", "final_norm", "norm1", "wq", "wk", "wv", "wo",
          "norm2", "router", "we_gate", "we_up", "we_down")
F32_LEAVES = frozenset()
draw = dense.draw


def shapes(dims: dict) -> dict:
    d, v, n = dims["d_model"], dims["vocab"], dims["n_layers"]
    e, f = dims["moe"]["n_experts"], dims["moe"]["d_ff_expert"]
    hq, hkv, dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    return {
        "embed": (v, d), "head": (d, v), "final_norm": (d,),
        "norm1": (n, d), "wq": (n, d, hq * dh), "wk": (n, d, hkv * dh),
        "wv": (n, d, hkv * dh), "wo": (n, hq * dh, d), "norm2": (n, d),
        "router": (n, d, e), "we_gate": (n, e, d, f), "we_up": (n, e, d, f),
        "we_down": (n, e, f, d),
    }


def work(dims: dict) -> dict:
    """As the dense decoder's, with the FFN's weights those of the
    router and of the ``top_k`` experts a token is routed to."""
    dm, dh, n = dims["d_model"], dims["head_dim"], dims["n_layers"]
    hq, hkv, moe = dims["n_heads"], dims["n_kv_heads"], dims["moe"]
    layer = (dm * (hq + 2 * hkv) * dh + hq * dh * dm + dm * moe["n_experts"]
             + moe["top_k"] * 3 * dm * moe["d_ff_expert"])
    return {"matmul_params": n * layer + dm * dims["vocab"],
            "norms": [[dm, 2 * n + 1]], "attn_layers": n,
            "attn_width": hq * dh, "kv_row": 2 * hkv * dh, "d_model": dm}


def _experts(h, lw, top_k, rnd):
    probs = jax.nn.softmax(dense._mm(h, lw["router"], rnd), -1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(idx, probs.shape[-1]) * gates[..., None]).sum(-2)

    def ein(spec, a, b):
        return rnd(jnp.einsum(spec, rnd(a), rnd(b), precision=dense.HIGHEST,
                              preferred_element_type=jnp.float32))
    g = ein("bsd,edf->ebsf", h, lw["we_gate"])
    u = ein("bsd,edf->ebsf", h, lw["we_up"])
    y = ein("ebsf,efd->ebsd", rnd(jax.nn.silu(g) * u), lw["we_down"])
    return rnd(jnp.einsum("bse,ebsd->bsd", rnd(weight), y,
                          precision=dense.HIGHEST))


def logits(w: dict, tokens, dims: dict, rnd=dense._identity):
    """[B, S] token ids → [B, S, vocab] float32 logits."""
    eps, top_k = dims["norm_eps"], dims["moe"]["top_k"]
    x = rnd(w["embed"].astype(jnp.float32)[tokens])
    layers = {k: w[k].astype(jnp.float32) for k in LEAVES[3:]}

    def layer(x, lw):
        h = dense._rmsnorm(x, lw["norm1"], eps, rnd)
        x = rnd(x + dense._attention(h, lw, dims, rnd))
        h = dense._rmsnorm(x, lw["norm2"], eps, rnd)
        return rnd(x + _experts(h, lw, top_k, rnd)), None

    x, _ = jax.lax.scan(layer, x, layers)
    x = dense._rmsnorm(x, w["final_norm"].astype(jnp.float32), eps, rnd)
    return dense._mm(x, w["head"].astype(jnp.float32), rnd)
