"""Plain reference of a VGG-style CNN training step, in jax.numpy.

Follows Simonyan & Zisserman (arXiv:1409.1556), Table 1: SAME 3x3
convolutions with bias and ReLU, a 2x2 stride-2 max-pool after every
``pool_every`` convolutions; the classifier is global average pooling and
one linear layer (the cut the configuration file names).  The loss is the
mean softmax cross-entropy.  The optimizer is AdamW (Loshchilov & Hutter)
with global-norm clipping and bias correction:

    g   <- g * min(1, clip / |g|)
    m   <- b1 m + (1 - b1) g          v <- b2 v + (1 - b2) g^2
    p   <- p - lr (m_hat / (sqrt(v_hat) + eps) + wd p)

Everything runs at the precision it is given (``highest`` for the f32
configuration), with ``lax.conv_general_dilated`` and no kernels, meshes
or custom gradients.  A batch too large for one chip is reduced in chunks:
the mean loss and its gradient are the means over equal chunks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def forward(params, images, *, pool_every: int, precision):
    x = images
    for i, blk in enumerate(params["convs"]):
        x = lax.conv_general_dilated(
            x, blk["w"], (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=precision)
        x = jnp.maximum(x + blk["b"][None, :, None, None], 0.0)
        if (i + 1) % pool_every == 0:
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 2, 2),
                                  (1, 1, 2, 2), "VALID")
    feats = jnp.mean(x, axis=(2, 3))
    return jnp.dot(feats, params["head"], precision=precision)


def loss(params, batch, *, pool_every: int, precision):
    logits = forward(params, batch["images"], pool_every=pool_every,
                     precision=precision)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], 1))


def make_loss_and_grad(*, pool_every: int, precision, chunk: int = 0):
    """``f(params, batch) -> (loss, grads)``; with ``chunk`` the batch is
    reduced ``chunk`` images at a time, one jitted call per chunk."""
    vg = jax.jit(jax.value_and_grad(
        lambda p, b: loss(p, b, pool_every=pool_every, precision=precision)))

    def f(params, batch):
        n = batch["labels"].shape[0]
        if not chunk or chunk >= n:
            return vg(params, batch)
        parts = n // chunk
        tot_l, tot_g = 0.0, None
        for j in range(parts):
            sl = {k: v[j * chunk:(j + 1) * chunk] for k, v in batch.items()}
            l, g = vg(params, sl)
            tot_l = tot_l + l
            tot_g = g if tot_g is None else jax.tree.map(jnp.add, tot_g, g)
        return tot_l / parts, jax.tree.map(lambda a: a / parts, tot_g)
    return f


def make_adamw(opt: dict):
    """``update(params, grads, m, v, t) -> (params, m, v, clipped g)``,
    one jitted AdamW step at step number ``t`` (1-based)."""
    b1, b2, lr, eps = opt["b1"], opt["b2"], opt["lr"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]

    @jax.jit
    def update(params, grads, m, v, t):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / (gnorm + 1e-12))
        g = jax.tree.map(lambda x: x * scale, grads)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        tf = t.astype(jnp.float32)
        c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf
        new = jax.tree.map(
            lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                        + wd * p), params, m, v)
        return new, m, v, g
    return update


def train_steps(params, batches, opt: dict, *, pool_every: int, precision,
                chunk: int = 0):
    """Run ``len(batches)`` reference steps from ``params``.

    Returns ``(losses, first clipped gradient, params after the steps)``.
    """
    lg = make_loss_and_grad(pool_every=pool_every, precision=precision,
                            chunk=chunk)
    update = make_adamw(opt)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        l, g = lg(params, batch)
        params, m, v, gc = update(params, g, m, v, jnp.int32(t))
        losses.append(float(l))
        if first is None:
            first = gc
    return losses, first, params
