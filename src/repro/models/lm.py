"""Unified causal LM (decoder-only; covers dense/ssm/moe/hybrid/vlm) and
the encoder-decoder variant (whisper) behind one API:

  init(key)                        → params
  loss(params, batch, ctx)         → (scalar, metrics)
  prefill(params, batch, ctx)      → (last_logits, cache)
  decode_step(params, batch, cache, pos, ctx) → (logits, cache)
  serving_params(params)           → params cast once for serving

batch keys: tokens [B,S] int32; optional prefix_embeds [B,Np,D] (vlm),
frames [B,Tenc,D] (audio stub).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, blocks, common, mamba2


def _reads_f32(path) -> bool:
    """Whether a layer reads the leaf at ``path`` at float32, not
    through a cast to the compute dtype."""
    names = [getattr(k, "key", None) for k in path]
    return "mamba" in names and names[-1] in mamba2.F32_LEAVES


def _embed_tokens(params, tokens, cfg: ModelConfig):
    return params["embed"].astype(cfg.cdtype())[tokens]


def _head_logits(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype).T
    else:
        w = params["head"].astype(x.dtype)
    return x @ w


def chunked_nll(params, x, labels, mask, cfg: ModelConfig,
                n_chunks: int = 8):
    """Cross-entropy without materializing [B,S,V] at once: scan over
    sequence chunks (memory: B*S/n*V per step)."""
    b, s, d = x.shape
    while s % n_chunks:
        n_chunks -= 1
    cs = s // n_chunks
    xr = x.reshape(b, n_chunks, cs, d).swapaxes(0, 1)
    lr = labels.reshape(b, n_chunks, cs).swapaxes(0, 1)
    mr = mask.reshape(b, n_chunks, cs).swapaxes(0, 1)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def _chunk_nll(xs, ls, ms):
        logits = _head_logits(params, xs, cfg).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:  # mask pad columns
            pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(pad_mask, logits, -1e30)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        nll = (lse - ll + 1e-4 * lse ** 2) * ms
        return nll.sum(), ms.sum()

    def body(carry, inp):
        xs, ls, ms = inp
        s_nll, s_cnt = _chunk_nll(xs, ls, ms)
        tot, cnt = carry
        return (tot + s_nll, cnt + s_cnt), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xr, lr, mr))
    return tot / jnp.maximum(cnt, 1.0)


@dataclasses.dataclass(frozen=True)
class CausalLM:
    cfg: ModelConfig

    # ---------------------------------------------------------- params
    def init(self, key) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        params = {
            "embed": common.embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                       cfg.pdtype()),
            "blocks": blocks.init_stack(ks[1], cfg),
            "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype()),
        }
        if not cfg.tie_embeddings:
            params["head"] = common.dense_init(
                ks[2], (cfg.d_model, cfg.padded_vocab), dtype=cfg.pdtype())
        return params

    # --------------------------------------------------------- helpers
    def _inputs(self, params, batch):
        cfg = self.cfg
        x = _embed_tokens(params, batch["tokens"], cfg)
        n_prefix = 0
        if cfg.n_prefix_embeds and "prefix_embeds" in batch:
            pre = batch["prefix_embeds"].astype(x.dtype)
            x = jnp.concatenate([pre, x], axis=1)
            n_prefix = pre.shape[1]
        return x, n_prefix

    def hidden(self, params, batch, ctx=None, remat=True):
        cfg = self.cfg
        x, n_prefix = self._inputs(params, batch)
        s = x.shape[1]
        rope = common.make_rope(jnp.arange(s), cfg.head_dim, cfg.rope_theta,
                                cfg.rope_style)
        x, aux = blocks.stack_forward(params["blocks"], x, cfg, rope, ctx,
                                      causal=True, remat=remat)
        x = common.rms_norm(x, params["final_norm"].astype(x.dtype),
                            cfg.norm_eps)
        return x, aux, n_prefix

    # ----------------------------------------------------------- train
    def loss(self, params, batch, ctx=None, remat=True):
        cfg = self.cfg
        x, aux, n_prefix = self.hidden(params, batch, ctx, remat)
        x = x[:, n_prefix:]
        tokens = batch["tokens"]
        labels = tokens[:, 1:]
        mask = jnp.ones_like(labels, jnp.float32)
        nll = chunked_nll(params, x[:, :-1], labels, mask, cfg)
        aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
        total = nll + aux_w * aux
        return total, {"nll": nll, "aux": aux}

    def logits(self, params, batch, ctx=None):
        x, _, n_prefix = self.hidden(params, batch, ctx, remat=False)
        out = _head_logits(params, x[:, n_prefix:], self.cfg)
        return out[..., :self.cfg.vocab_size]

    # ----------------------------------------------------------- serve
    def serving_params(self, params) -> dict:
        """``params`` as a server holds them: each floating leaf that the
        layers read through ``.astype(compute dtype)`` cast to that dtype
        once, in one jitted call (elementwise, so each leaf keeps its
        sharding); the leaves a layer reads at float32
        (``mamba2.F32_LEAVES``) as given.  The decode step's casts are
        then no-ops: the same values reach the same matmuls, rounded here
        once instead of in every step."""
        cdt = self.cfg.cdtype()
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [a for _, a in flat]
        idx = [i for i, (path, a) in enumerate(flat)
               if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != cdt
               and not _reads_f32(path)]
        cast = jax.jit(lambda xs: [x.astype(cdt) for x in xs])
        for i, a in zip(idx, cast([leaves[i] for i in idx])):
            leaves[i] = a
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def init_cache(self, batch: int, max_len: int):
        return blocks.init_stack_cache(self.cfg, batch, max_len,
                                       self.cfg.cdtype())

    def prefill(self, params, batch, ctx=None, max_len: Optional[int] = None):
        """Single-pass prefill: hidden states AND caches from one scan
        (the two-pass variant doubled prefill compute; §Perf)."""
        cfg = self.cfg
        x, n_prefix = self._inputs(params, batch)
        s = x.shape[1]
        rope = common.make_rope(jnp.arange(s), cfg.head_dim, cfg.rope_theta,
                                cfg.rope_style)
        b = batch["tokens"].shape[0]
        max_len = max_len or cfg.max_seq
        cache = self.init_cache(b, max_len)
        x, cache = blocks.stack_prefill(params["blocks"], cache, x, cfg,
                                        rope, ctx)
        x = common.rms_norm(x, params["final_norm"].astype(x.dtype),
                            cfg.norm_eps)
        logits = _head_logits(params, x[:, -1:], cfg)[:, 0,
                                                      :cfg.vocab_size]
        return logits, cache

    def decode_step(self, params, tokens, cache, pos, ctx=None,
                    shards: int = 1):
        """tokens: [B, 1]; pos: scalar int32 current length, or a [B]
        vector of per-row lengths (ragged continuous batching — one
        compiled step serves slots at different positions)."""
        cfg = self.cfg
        x = _embed_tokens(params, tokens, cfg)
        pos = jnp.asarray(pos, jnp.int32)
        rope = common.make_rope(pos[:, None] if pos.ndim else pos[None],
                                cfg.head_dim, cfg.rope_theta,
                                cfg.rope_style)
        x, newcache = blocks.stack_decode(params["blocks"], cache, x, cfg,
                                          rope, pos, ctx, shards=shards)
        x = common.rms_norm(x, params["final_norm"].astype(x.dtype),
                            cfg.norm_eps, common.kernel_mesh(ctx, shards))
        return (_head_logits(params, x, cfg)[:, 0, :cfg.vocab_size],
                newcache)


def build_model(cfg: ModelConfig):
    if cfg.encdec:
        from repro.models.whisper import EncDecLM
        return EncDecLM(cfg)
    return CausalLM(cfg)
