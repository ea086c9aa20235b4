"""The plain reference: the served model's forward pass in float32.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching, one sequence at a time. It imports nothing of the
program and takes nothing the program made. It draws the seed's weights
itself (``bench/lib/weights``) and applies the deployment's stated
precision to them with its own code:

* projections: MX quantization to the anchor (OCP MX: one power-of-two
  scale per block of 32 along the contraction, ``floor(log2 max|w|) -
  emax``; codes rounded half to even and clipped to the symmetric range),
  then, for a lower rung, Slice-and-Scale (codes shifted right by ``bits_hi
  - bits_lo`` with rounding half to even, scale raised by as much);
* every other leaf (embedding, lm_head, norm gains, biases): bfloat16;
* activations: float32 throughout.

The layer equations are the model as the configuration file's ``model``
block runs it: pre-norm RMSNorm blocks, q/k/v projections with optional
biases, optional per-head RMSNorm of q and k, rotary embedding on two
halves, causal grouped-query attention (within the sliding window, where
there is one), an output projection, then a SwiGLU or tanh-GELU MLP with
optional biases; a final RMSNorm and the lm_head (the embedding's
transpose where they are tied). Where that departs from the published model, the configuration
file says so.

``act_dtype`` computes every projection's input in a lower precision
(``float8_e4m3fn``): the control, which a sound comparison must fail.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.weights import base_key, global_weights, layer_shapes, \
    layer_weights

HI = jax.lax.Precision.HIGHEST
PAD = 128            # sequences are padded to PAD * 2**k tokens


def _floor_log2(x):
    _, e = jnp.frexp(x)
    return e - 1


def mx_codes(w: jax.Array, bits: int, block_size: int):
    """(codes, scale exponents) of ``w`` (K, N), blocks along K."""
    k, n = w.shape
    wb = w.reshape(k // block_size, block_size, n)
    bmax = jnp.max(jnp.abs(wb), axis=1, keepdims=True)
    emax = bits - 2
    e = jnp.where(bmax > 0, _floor_log2(jnp.where(bmax > 0, bmax, 1.0)),
                  -127 + emax) - emax
    e = jnp.clip(e, -127, 127)
    maxq = 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(wb / jnp.exp2(e.astype(jnp.float32))), -maxq, maxq)
    return q, e


def rung_weight(w: jax.Array, anchor_bits: int, bits: int,
                block_size: int) -> jax.Array:
    """``w`` as served at ``bits``: quantized to the anchor, then
    Slice-and-Scaled down to the rung; dequantized to float32."""
    q, e = mx_codes(w, anchor_bits, block_size)
    de = anchor_bits - bits
    if de:
        maxq = 2 ** (bits - 1) - 1
        q = jnp.clip(jnp.round(q / 2.0 ** de), -maxq, maxq)
        e = jnp.clip(e + de, -127, 127)
    return (q * jnp.exp2(e.astype(jnp.float32))).reshape(w.shape)


def bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """x (T, H, D): rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


class Reference:
    """Float32 logits of the served model, computed layer by layer."""

    def __init__(self, m: dict, seed: int, *, anchor_bits: int, bits: int,
                 block_size: int = 32, act_dtype=None):
        self.m = m
        self.key = base_key(seed)
        self.anchor_bits, self.bits = anchor_bits, bits
        self.block_size = block_size
        self.act_dtype = act_dtype
        self._layer_w = jax.jit(self._make_layer)
        self._apply = jax.jit(self._apply_layer)
        self._gap_j = jax.jit(self._gap)
        self._first_j = jax.jit(self._first)

    # ---- weights -----------------------------------------------------------
    def _make_layer(self, key, layer):
        kinds = {n: k for n, (_, k) in layer_shapes(self.m).items()}
        out = {}
        for name, w in layer_weights(key, self.m, layer).items():
            out[name] = rung_weight(w, self.anchor_bits, self.bits,
                                    self.block_size) \
                if kinds[name] in ("proj", "out") else bf16(w)
        return out

    def _dot(self, x, w):
        if self.act_dtype is not None:
            x = x.astype(self.act_dtype).astype(jnp.float32)
        return jnp.dot(x, w, precision=HI)

    # ---- one layer over one padded sequence ---------------------------------
    def _apply_layer(self, x, w):
        m = self.m
        t = x.shape[0]
        h_, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
        eps, pos = m["norm_eps"], jnp.arange(t)
        h = rms_norm(x, w["mixer_norm"], eps)
        q = self._dot(h, w["attn.wq"])
        k = self._dot(h, w["attn.wk"])
        v = self._dot(h, w["attn.wv"])
        if m["qkv_bias"]:
            q, k, v = q + w["attn.bq"], k + w["attn.bk"], v + w["attn.bv"]
        q, k, v = (q.reshape(t, h_, hd), k.reshape(t, kv, hd),
                   v.reshape(t, kv, hd))
        if m["qk_norm"]:
            q = rms_norm(q, w["attn.q_norm"], eps)
            k = rms_norm(k, w["attn.k_norm"], eps)
        q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
        g = h_ // kv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        live = pos[:, None] >= pos[None, :]
        if m.get("sliding_window"):
            live &= pos[:, None] - pos[None, :] < m["sliding_window"]
        s = jnp.where(live[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, h_ * hd)
        x = x + self._dot(o, w["attn.wo"])
        h = rms_norm(x, w["ffn_norm"], eps)
        if m["act"] == "swiglu":
            a = jax.nn.silu(self._dot(h, w["mlp.w_gate"])) \
                * self._dot(h, w["mlp.w_up"])
        else:
            up = self._dot(h, w["mlp.w_up"])
            a = gelu_tanh(up + w["mlp.b_up"] if m["mlp_bias"] else up)
        y = self._dot(a, w["mlp.w_down"])
        if m["mlp_bias"]:
            y = y + w["mlp.b_down"]
        return x + y

    # ---- head ----------------------------------------------------------------
    def _logits(self, h, lm_head, final_norm):
        return jnp.dot(rms_norm(h, final_norm, self.m["norm_eps"]), lm_head,
                       precision=HI)

    def _gap(self, h, lm_head, final_norm, tok):
        """Per row: best logit minus the logit of ``tok``."""
        logits = self._logits(h, lm_head, final_norm)
        return jnp.max(logits, -1) \
            - jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]

    def _first(self, h, lm_head, final_norm):
        return jnp.argmax(self._logits(h, lm_head, final_norm), -1)

    def hidden(self, seqs: Sequence[np.ndarray]) -> List[jax.Array]:
        """Last hidden states (T, d), before the final norm, of each
        sequence."""
        glob = global_weights(self.key, self.m)
        embed = bf16(glob["embed"])
        xs = []
        for s in seqs:
            t = PAD
            while t < len(s):
                t *= 2
            toks = np.zeros(t, np.int32)
            toks[:len(s)] = s
            xs.append(jnp.take(embed, jnp.asarray(toks), axis=0))
        del embed, glob
        for layer in range(self.m["n_layers"]):
            w = self._layer_w(self.key, layer)
            xs = [self._apply(x, w) for x in xs]
        return [x[:len(s)] for x, s in zip(xs, seqs)]

    def head_weights(self):
        glob = global_weights(self.key, self.m)
        head = glob["embed"].T if self.m["tie_embeddings"] \
            else glob["lm_head"]
        return bf16(head), bf16(glob["final_norm"])


ROWS = 256           # lm_head rows per block


def _blocks(rows: jax.Array):
    """``rows`` in blocks of ROWS, the last one zero-padded."""
    n = rows.shape[0]
    for i in range(0, n, ROWS):
        blk = rows[i:i + ROWS]
        if blk.shape[0] < ROWS:
            blk = jnp.pad(blk, ((0, ROWS - blk.shape[0]), (0, 0)))
        yield i, min(ROWS, n - i), blk


def served_gaps(ref: Reference, prompts: Sequence[np.ndarray],
                served: Sequence[Sequence[int]],
                control: Optional[Reference] = None):
    """Per request, per served token: the reference's best logit minus its
    logit of the token that was served or, with ``control``, of the token
    the control puts first at that position (the control in the program's
    place, over the same prompts and served tokens).

    Each prompt is run once with its served tokens; the logits that chose
    served token ``j`` sit at position ``len(prompt) - 1 + j``. Returns one
    array of gaps per request.
    """
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, served)]
    picks = None
    if control is not None:
        c_head, c_norm = control.head_weights()
        picks = []
        for h, p in zip(control.hidden(seqs), prompts):
            picks.append(np.concatenate([
                np.asarray(control._first_j(blk, c_head, c_norm))[:n]
                for _, n, blk in _blocks(h[len(p) - 1:])]))
        del c_head, c_norm
    hs = ref.hidden(seqs)
    lm_head, fnorm = ref.head_weights()

    def gaps_at(h, tok):
        out = []
        for i, n, blk in _blocks(h):
            t = np.zeros(ROWS, np.int32)
            t[:n] = tok[i:i + n]
            out.append(np.asarray(ref._gap_j(blk, lm_head, fnorm,
                                             jnp.asarray(t)),
                                  np.float64)[:n])
        return np.concatenate(out)

    rows = [h[len(p) - 1:] for h, p in zip(hs, prompts)]
    toks = served if picks is None else picks
    return [gaps_at(r, np.asarray(t, np.int32)) for r, t in zip(rows, toks)]
