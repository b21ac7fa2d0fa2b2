"""The plain reference of the dense decoder block both configurations
use, in float32, and its control in float8.

It is written from the block's equations and imports nothing of the
program: pre-norm residual blocks of RMSNorm, grouped-query attention
with rotary embeddings (half-split, base ``rope_theta``) and a causal
softmax, then an FFN that is either gated (``silu(x Wg) * x Wu``) or
plain (``gelu_tanh(x Wu)``), a final RMSNorm and the LM head (the
embedding, when tied).  Every matrix product runs at
``precision=HIGHEST``, so a TPU computes it in float32.

``lowp=True`` is the control: the same mathematics with every operand
of every matrix product rounded to float8 (e4m3), the step below the
configuration's bfloat16 that a later change could be tempted to take.

The weights come from :mod:`model`, which makes them from the seed: the
reference takes nothing that the program has made.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
PAD = 1024          # sequences are padded to a multiple of this
Q_BLOCK = 512       # queries per attention block
HEAD_ROWS = 256     # rows per LM-head block


def _q(x, lowp: bool):
    if not lowp:
        return x
    x = jnp.clip(x, -F8_MAX, F8_MAX)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(spec, a, b, lowp):
    return jnp.einsum(spec, _q(a, lowp), _q(b, lowp), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # (L, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, lowp):
    """Causal GQA: q (L, H, D), k/v (L, KV, D) -> (L, H, D)."""
    n, h, dh = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(n, kv, g, dh) / math.sqrt(dh)
    blocks = []
    for s in range(0, n, Q_BLOCK):
        qb = qg[s:s + Q_BLOCK]
        sc = _mm("qkgd,lkd->kgql", qb, k, lowp)           # (KV, G, qb, L)
        qpos = s + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(n)[None, :] <= qpos, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        blocks.append(_mm("kgql,lkd->qkgd", p, v, lowp))
    return jnp.concatenate(blocks, 0).reshape(n, h, dh)


def _hidden(c, w, tokens, lowp):
    """Final normed hidden states (L, d) of one padded sequence."""
    blk = c["block"]
    eps, theta = blk["norm_eps"], blk["rope_theta"]
    gated = c["hidden_act"] == "silu-gated"
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)
    names = ["norm_mixer", "wq", "wk", "wv", "wo", "norm_ffn", "w_up",
             "w_down"] + (["w_gate"] if gated else [])

    def layer(x, lw):
        lw = {k: a.astype(jnp.float32) for k, a in zip(names, lw)}
        h = _rms(x, lw["norm_mixer"], eps)
        q = _rope(_mm("ld,dhk->lhk", h, lw["wq"], lowp), pos, theta)
        k = _rope(_mm("ld,dhk->lhk", h, lw["wk"], lowp), pos, theta)
        v = _mm("ld,dhk->lhk", h, lw["wv"], lowp)
        x = x + _mm("lhk,hkd->ld", _attention(q, k, v, lowp), lw["wo"], lowp)
        h = _rms(x, lw["norm_ffn"], eps)
        up = _mm("ld,df->lf", h, lw["w_up"], lowp)
        if gated:
            up = jax.nn.silu(_mm("ld,df->lf", h, lw["w_gate"], lowp)) * up
        else:
            up = jax.nn.gelu(up, approximate=True)
        return x + _mm("lf,fd->ld", up, lw["w_down"], lowp), None

    x, _ = jax.lax.scan(layer, x, [w[n] for n in names])
    return _rms(x, w["final_norm"].astype(jnp.float32), eps)


def _head(c, w):
    if c["tie_word_embeddings"]:
        return w["embed"], "rd,vd->rv"
    return w["lm_head"], "rd,dv->rv"


@functools.partial(jax.jit, static_argnums=(0, 4))
def _rows(c_key, w, tokens, targets, lowp):
    """Per row of the padded sequence: (best logit, logit of the target,
    best token), under the reference (or, lowp, the control)."""
    c = json.loads(c_key)
    hid = _hidden(c, w, tokens, lowp)
    head, spec = _head(c, w)

    def block(args):
        rows, tgt = args
        lg = _mm(spec, rows, head.astype(jnp.float32), lowp)
        at = jnp.take_along_axis(lg, tgt[:, None], -1)[:, 0]
        return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)

    n = hid.shape[0]
    return jax.lax.map(block, (hid.reshape(n // HEAD_ROWS, HEAD_ROWS, -1),
                               targets.reshape(n // HEAD_ROWS, HEAD_ROWS)))


def _run(c, w, seq, targets, lowp):
    n = len(seq)
    size = -(-n // PAD) * PAD
    tok = np.zeros(size, np.int32)
    tok[:n] = seq
    tgt = np.zeros(size, np.int32)
    tgt[:n] = targets
    best, at, arg = _rows(json.dumps(c, sort_keys=True), w, jnp.asarray(tok), jnp.asarray(tgt),
                          lowp)
    return (np.asarray(best).reshape(-1)[:n], np.asarray(at).reshape(-1)[:n],
            np.asarray(arg).reshape(-1)[:n])


def served_gaps(c: dict, w: dict, prompt: list, served: list,
                control: bool = False) -> np.ndarray:
    """Per served token: how far the reference's logit of the token lies
    below the reference's best logit at that position.

    ``control=False`` reads the tokens the program served.  With
    ``control=True`` the tokens read are those the float8 control puts
    first at each of the same positions (teacher-forced on the same
    prompt and served tokens), so the number is the control's."""
    seq = list(prompt) + list(served)
    p = len(prompt)
    targets = seq[1:] + [0]
    if control:
        _, _, arg = _run(c, w, seq, targets, True)
        targets = list(map(int, arg))
    best, at, _ = _run(c, w, seq, targets, False)
    rows = slice(p - 1, len(seq) - 1)
    return best[rows] - at[rows]
