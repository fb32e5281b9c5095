"""Whether the timed path served the right tokens.

After the window, a sample of the requests the engine finished there,
drawn from the seed and always holding the longest, is replayed through
the plain reference: one forward pass over each prompt with its served
tokens.  At every served position the reference's best logit minus its
logit of the served token is the gap; the number compared is the widest
gap over the sample.  Greedy decoding at the configuration's precision
keeps it near the rounding of that precision; a wrong cache row,
position, mask, merge or token puts it far above.

The control runs the same reference with every operand rounded through
float8 (e4m3), the step below the configuration's bfloat16: at each
position it reads the gap of the token that precision puts first.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic

BATCH = 4               # sequences per reference call


def sample(finished: list, mix: dict, seed: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until the sample serves ``check_tokens`` tokens."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt), -r.uid))
    rest = [r for r in finished if r is not longest]
    order = traffic.rng_for(seed, 2).permutation(len(rest))
    out, n = [longest], len(longest.served)
    for i in order:
        if n >= mix["check_tokens"]:
            break
        out.append(rest[i])
        n += len(rest[i].served)
    return out


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("ref", "dims_json",
                                              "control"))
def _gaps(w, toks, nxt, valid, ref, dims_json, control):
    dims = json.loads(dims_json)
    lg = ref.logits(w, toks, dims)
    best = lg.max(-1)
    gap = best - jnp.take_along_axis(lg, nxt[..., None], -1)[..., 0]
    out = jnp.where(valid, gap, -jnp.inf).max(-1)
    if not control:
        return out, out
    pick = ref.logits(w, toks, dims, rnd=_fp8).argmax(-1)
    cgap = best - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
    return out, jnp.where(valid, cgap, -jnp.inf).max(-1)


def gaps(ref, w: dict, dims: dict, reqs: list, length: int,
         control: bool = False) -> tuple[list, list]:
    """Per request, the widest gap of its served tokens (and, with
    ``control``, of the float8 reference's first choices) at the same
    positions.  Sequences are padded to ``length``; the attention is
    causal, so padding never reaches a served position."""
    dims_json = json.dumps(dims, sort_keys=True)    # hashable, nested too
    got, ctl = [], []
    for i in range(0, len(reqs), BATCH):
        chunk = reqs[i:i + BATCH]
        toks = np.zeros((BATCH, length), np.int32)
        nxt = np.zeros((BATCH, length), np.int32)
        valid = np.zeros((BATCH, length), bool)
        for b, r in enumerate(chunk):
            seq = np.concatenate([r.prompt, np.asarray(r.served, np.int32)])
            p, n = len(r.prompt), len(r.served)
            toks[b, :len(seq) - 1] = seq[:-1]
            nxt[b, p - 1:p - 1 + n] = r.served
            valid[b, p - 1:p - 1 + n] = True
        g, c = _gaps(w, jnp.asarray(toks), jnp.asarray(nxt),
                     jnp.asarray(valid), ref, dims_json, control)
        got += [float(x) for x in np.asarray(g)[:len(chunk)]]
        ctl += [float(x) for x in np.asarray(c)[:len(chunk)]]
    return got, (ctl if control else [])


def passes(compared: dict) -> bool:
    """Every number compared has a value within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())


def unserved(loop, vocab: int) -> int:
    """Answers that never came or do not add up: a finished request
    whose tokens from ``run()``, count in ``serve.request`` and stamped
    steps disagree, or hold an id outside the vocabulary; plus every
    step the loop could not attribute."""
    bad = len(loop.errors)
    for r in loop.finished():
        toks = r.served or []
        if (len(toks) != len(r.stamps) or r.retired_tokens not in
                (None, len(r.stamps))
                or any(not 0 <= t < vocab for t in toks)):
            bad += 1
    return bad
