"""Plain reference of a Llama-style decoder (SmolLM), in jax.numpy.

The published description (HF ``LlamaForCausalLM``): token embedding;
per layer, RMSNorm -> grouped-query attention with rotary embeddings
(rotate-half form, frequencies ``theta^(-2i/d)``), residual add,
RMSNorm -> SwiGLU MLP, residual add; final RMSNorm and the LM head.
Query head ``i`` reads key/value head ``i // (heads / kv_heads)``.

Departures, both of layout and not of arithmetic: the LM head is its own
matrix (the configuration unties it), and each RMSNorm scale is stored as
its offset from 1, so the scale applied is ``1 + stored``.

The forward runs over a whole sequence at once, with no cache, in float32
from the served bfloat16 weights, at ``highest`` matmul precision.
``quant="fp8"`` is the control of a bfloat16 configuration: every matmul's
two operands are rounded to float8 e4m3 with one scale per tensor before
the product (``quant="bf16"``, to bfloat16, for a float32 one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    elif quant == "bf16":
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return jnp.matmul(x, w, precision=_HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    s, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs       # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, cfg: dict, quant: str = ""):
    """tokens [S] int32 -> logits [S, vocab] float32."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    h = params["emb"]["tok"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, blk):
        blk = f32(blk)
        x = _rmsnorm(h, blk["ln1"], eps)
        q = _rope(_mm(x, blk["attn"]["wq"], quant).reshape(s, nh, hd), theta)
        k = _rope(_mm(x, blk["attn"]["wk"], quant).reshape(s, nkv, hd),
                  theta)
        v = _mm(x, blk["attn"]["wv"], quant).reshape(s, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI)
        h = h + _mm(o.reshape(s, nh * hd), blk["attn"]["wo"], quant)
        x = _rmsnorm(h, blk["ln2"], eps)
        gate = _mm(x, blk["mlp"]["w_gate"], quant)
        up = _mm(x, blk["mlp"]["w_up"], quant)
        h = h + _mm(jax.nn.silu(gate) * up, blk["mlp"]["w_down"], quant)
        return h, None

    h, _ = lax.scan(layer, h, params["blocks"])
    h = _rmsnorm(h, params["ln_f"].astype(jnp.float32), eps)
    return _mm(h, params["emb"]["lm_head"].astype(jnp.float32), quant)
