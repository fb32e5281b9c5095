"""``TraversalSpec`` builder for the decode-attention family.

This spec IS the flash-decode kernel now: the hand-written Pallas body
(``decode_attn.py``) was retired once the generated variant had matched
it for a full release cycle (ROADMAP retirement plan); ``ops.py`` and
the ``decode_attn_gen`` registry variant both lower this builder
through ``repro.codegen``.

ONE *stride-axis reduction* sweep over the KV cache (``b`` a batch grid
dim, the sequence axis split into D streams): the sweep is reduced with
the paired-state :class:`~repro.codegen.OnlineSoftmax` combinator, so
each block's (max, rescaled Σ softmax·V, rescaled Σ w) partial state
merges numerically-stably across the D merged streams and grid steps
and K/V are each read exactly once.  The combinator's finalize ALSO
emits the per-row log-sum-exp as a second native output (its own
``Hq``-wide access map).  q arrives as ``[b, Hq, dh]`` and the body
works one KV head at a time in 2-D matmuls, keeping the combinator's
``[Hq, …]`` state layout: the forms Mosaic compiles.

``masked=True`` adds a fourth read: a per-position validity row stream
``M`` (1.0 = attend, 0.0 = masked) riding the same D-stream split as
K/V — masked positions drop to ``-1e30`` before the block max, so their
weights vanish inside the block and fully-masked blocks are rescaled
away by the online merge.  The wrapper selects it only when a
``kv_len`` is actually supplied (which may be a traced scalar — the
models' decode loop), keeping the default plan at two operand streams.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.codegen import Access, Axis, OnlineSoftmax, TraversalSpec

__all__ = ["decode_spec"]


@functools.lru_cache(maxsize=None)
def decode_spec(hkv: int, dh: int, masked: bool = False):
    """Per-(Hkv, dh) single-pass spec builder (the head split is a
    static reshape inside the body).  The body emits the online-softmax
    partial state for its KV block; the ``OnlineSoftmax`` combinator
    merges states across the D streams and the sequence grid and
    finalizes ``num / den`` into the output — one K sweep, one V sweep.
    """

    def spec(kc2, vc2, q3, *mask):
        b, s, e = kc2.shape
        hq = q3.shape[1]
        g = hq // hkv
        scale = 1.0 / (dh ** 0.5)

        def body(env):
            # one KV head at a time, as 2-D matmuls with one batch dim:
            # the forms Mosaic lowers.  State comes out [b, Hq, …], so
            # nothing is reshaped across lanes
            ms, nums, dens = [], [], []
            for h in range(hkv):
                lanes = slice(h * dh, (h + 1) * dh)
                kh = env["K"][:, :, lanes].astype(jnp.float32)
                vh = env["V"][:, :, lanes].astype(jnp.float32)
                qh = env["q"][:, h * g:(h + 1) * g, :].astype(jnp.float32)
                sc = jnp.einsum("bgd,bsd->bgs", qh, kh) * scale
                if masked:
                    sc = jnp.where(env["M"][:, None, :] > 0.5, sc, -1e30)
                m = sc.max(axis=-1, keepdims=True)            # (b, g, 1)
                w = jnp.exp(sc - m)
                ms.append(m)
                nums.append(jnp.einsum("bgs,bsd->bgd", w, vh))
                dens.append(w.sum(axis=-1, keepdims=True))
            return (jnp.concatenate(ms, axis=1),
                    jnp.concatenate(nums, axis=1),
                    jnp.concatenate(dens, axis=1))

        reads = (Access("K", ("b", "s", "e")),
                 Access("V", ("b", "s", "e")),
                 Access("q", ("b", "h", "x")))
        if masked:
            reads += (Access("M", ("b", "s")),)

        return TraversalSpec(
            name="decode_attn_masked" if masked else "decode_attn_spec",
            axes=(Axis("b", b, kind="batch"),
                  Axis("s", s, kind="reduction"), Axis("e", e),
                  Axis("h", hq), Axis("x", dh)),
            reads=reads,
            # two writes, two access maps: the [Hq, dh] attention rows
            # and the Hq-wide log-sum-exp statistic — both finalized
            # from ONE accumulated online-softmax state
            writes=(Access("o", ("b", "h", "x")), Access("lse", ("b", "h"))),
            body=body, out_dtype=(jnp.float32, jnp.float32),
            reduce=OnlineSoftmax(groups=hq, vwidth=dh, with_lse=True),
            full_width=True,
        )

    return spec
