"""The weights of a two-kind hybrid decoder (a test configuration,
shaped like ``repro.configs.reduced`` of Jamba): periods of 8 layers, a
GQA attention layer at position 4 and Mamba-2 layers elsewhere, an
expert layer (4 experts, top 2) at the odd positions and a SwiGLU FFN at
the even ones, tied embeddings.

Only the layout half of a reference, what ``bench.weights`` and
``bench.program`` read: its leaves, their shapes and draws.  Each
per-layer leaf is stacked over the layers that hold it, in depth order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.refs import dense_decoder as dense

LEAVES = ("embed", "head", "final_norm", "norm1", "norm2",
          "wq", "wk", "wv", "wo",
          "in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
          "gate_norm", "out_proj",
          "w_gate", "w_up", "w_down",
          "router", "we_gate", "we_up", "we_down")
# read at float32 by the Mamba-2 layer, whatever the param dtype
F32_LEAVES = frozenset({"dt_bias", "a_log", "d_skip"})


def kinds(dims: dict) -> dict:
    """The layers of each kind."""
    n, period = dims["n_layers"], dims["attn_period"]
    every = dims["moe"]["every_n_layers"]
    attn = [i for i in range(n) if i % period == dims["attn_offset"]]
    moe = [i for i in range(n) if i % every == every - 1]
    return {"attn": len(attn), "mamba": n - len(attn), "moe": len(moe),
            "ffn": n - len(moe)}


def shapes(dims: dict) -> dict:
    d, v, n, f = dims["d_model"], dims["vocab"], dims["n_layers"], \
        dims["d_ff"]
    hq, hkv, dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    ssm, moe, k = dims["ssm"], dims["moe"], kinds(dims)
    di = ssm["expand"] * d
    nh, gn = di // ssm["head_dim"], ssm["n_groups"] * ssm["d_state"]
    na, nm, ne, nf = k["attn"], k["mamba"], k["moe"], k["ffn"]
    e, fe = moe["n_experts"], moe["d_ff_expert"]
    out = {
        "embed": (v, d), "head": (d, v), "final_norm": (d,),
        "norm1": (n, d), "norm2": (n, d),
        "wq": (na, d, hq * dh), "wk": (na, d, hkv * dh),
        "wv": (na, d, hkv * dh), "wo": (na, hq * dh, d),
        "in_proj": (nm, d, 2 * di + 2 * gn + nh),
        "conv_w": (nm, ssm["d_conv"], di + 2 * gn),
        "conv_b": (nm, di + 2 * gn), "dt_bias": (nm, nh), "a_log": (nm, nh),
        "d_skip": (nm, nh), "gate_norm": (nm, di), "out_proj": (nm, di, d),
        "w_gate": (nf, d, f), "w_up": (nf, d, f), "w_down": (nf, f, d),
        "router": (ne, d, e), "we_gate": (ne, e, d, fe),
        "we_up": (ne, e, d, fe), "we_down": (ne, e, fe, d),
    }
    if dims.get("tie_embeddings"):
        del out["head"]
    return out


def draw(name: str, key, shape: tuple):
    """The dense decoder's draws, with the Mamba-2 leaves' own: decay
    rates log U(1, 16), dt biases U(-4, -2) (softplus ~0.02-0.13), skips
    U(0.5, 1.5), conv taps N(0, 0.01), conv biases 0, the gated norm's
    scales as a norm's."""
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1, 16))
    if name == "dt_bias":
        return jax.random.uniform(key, shape, jnp.float32, -4, -2)
    if name == "d_skip":
        return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    if name == "conv_w":
        return jax.random.normal(key, shape, jnp.float32) * 0.1
    if name == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    if name == "gate_norm":
        return dense.draw("norm1", key, shape)
    return dense.draw(name, key, shape)
