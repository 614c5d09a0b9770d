"""Common functionals: linear, dropout, pad, embedding, attention.

Reference: python/paddle/nn/functional/{common,input}.py. linear keeps paddle's
weight layout [in_features, out_features] (x @ W + b), which is already the
MXU-friendly layout. Dropout draws from the framework RNG (core/random.py) so
it is deterministic under paddle.seed and stageable under jit via
trace_key_scope.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core import random as _random
from ...core.dispatch import apply

__all__ = [
    "linear", "dropout", "dropout2d", "dropout3d", "pad", "embedding",
    "cosine_similarity", "interpolate", "upsample", "unfold", "fold",
    "scaled_dot_product_attention", "alpha_dropout", "label_smooth",
    "pixel_shuffle", "pixel_unshuffle", "affine_grid", "grid_sample",
    "temporal_shift",
]


def linear(x, weight, bias=None, name=None):
    """x @ W + b with W: [in, out] (reference: F.linear, weight NOT transposed)."""
    from ...core.enforce import check_linear
    check_linear(x.shape, weight.shape,
                 bias.shape if bias is not None else None)

    def fwd(a, w, *b):
        out = jnp.matmul(a, w)
        if b:
            out = out + b[0]
        return out
    ins = [x, weight] + ([bias] if bias is not None else [])
    return apply("linear", fwd, ins)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    """Reference: python/paddle/nn/functional/common.py:967 (dropout)."""
    if p == 0.0 or (not training and mode == "upscale_in_train"):
        return x * 1 if not x.stop_gradient else x
    if p == 1.0 and training:
        return x * 0
    key = _random.next_key() if training else None

    def fwd(a):
        if not training:  # downscale_in_infer
            return a * (1 - p)
        shape = list(a.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1 - p, shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    return apply("dropout", fwd, [x])


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0:
        return x * 1 if not x.stop_gradient else x
    alpha = -1.7580993408473766
    key = _random.next_key()

    def fwd(a):
        keep = jax.random.bernoulli(key, 1 - p, a.shape)
        q = 1 - p
        a_scale = (q + alpha ** 2 * q * p) ** -0.5
        b_shift = -a_scale * alpha * p
        return (a_scale * jnp.where(keep, a, alpha) + b_shift).astype(a.dtype)
    return apply("alpha_dropout", fwd, [x])


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):  # noqa: A002
    """paddle F.pad: `pad` is [lo, hi] per spatial dim (last-dims order) when
    len(pad) == 2*(ndim-2), else per-dim pairs for all dims."""
    nd = x.ndim

    def build_pairs():
        p = list(int(v) for v in pad)
        if len(p) == 2 * nd:  # all dims, flat
            return [(p[2 * i], p[2 * i + 1]) for i in range(nd)]
        n_sp = len(p) // 2
        pairs = [(0, 0)] * nd
        channel_last = data_format[-1] == "C"
        sp_axes = list(range(1, 1 + n_sp)) if channel_last else \
            list(range(nd - n_sp, nd))
        # paddle order: last spatial dim first in `pad`? No: [left, right,
        # top, bottom] pads W then H → reversed spatial order
        for i, ax in enumerate(reversed(sp_axes)):
            pairs[ax] = (p[2 * i], p[2 * i + 1])
        return pairs

    pairs = build_pairs()

    def fwd(a):
        if mode == "constant":
            return jnp.pad(a, pairs, constant_values=value)
        jmode = {"reflect": "reflect", "replicate": "edge",
                 "circular": "wrap"}[mode]
        return jnp.pad(a, pairs, mode=jmode)
    return apply("pad", fwd, [x])


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Reference: python/paddle/nn/functional/input.py (embedding).
    Gather rows of weight; padding_idx rows get zero gradient."""
    from ...core.enforce import check_embedding
    check_embedding(x.dtype, weight.shape)

    def fwd(ids, w):
        out = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out
    return apply("embedding", fwd, [x, weight])


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def fwd(a, b):
        num = (a * b).sum(axis=axis)
        na = jnp.sqrt((a * a).sum(axis=axis))
        nb = jnp.sqrt((b * b).sum(axis=axis))
        return num / jnp.maximum(na * nb, eps)
    return apply("cosine_similarity", fwd, [x1, x2])


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    assert data_format in ("NCHW", "NCL", "NCDHW"), data_format
    n_sp = x.ndim - 2
    in_sp = x.shape[2:]
    if size is None:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else \
            [scale_factor] * n_sp
        size = [int(s * f) for s, f in zip(in_sp, sf)]
    elif isinstance(size, int):
        size = [size] * n_sp
    size = [int(s) for s in size]
    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def fwd(a):
        out_shape = tuple(a.shape[:2]) + tuple(size)
        return jax.image.resize(a, out_shape, method=jmode)
    return apply("interpolate", fwd, [x])


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format, name)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference: F.unfold). Output [N, C*kh*kw, L]."""
    kh, kw = (kernel_sizes, kernel_sizes) if isinstance(kernel_sizes, int) \
        else kernel_sizes
    sh, sw = (strides, strides) if isinstance(strides, int) else strides
    ph, pw = (paddings, paddings) if isinstance(paddings, int) else paddings
    dh, dw = (dilations, dilations) if isinstance(dilations, int) else dilations

    def fwd(a):
        n, c, h, w = a.shape
        pads = [(ph, ph), (pw, pw)]  # spatial dims only
        patches = jax.lax.conv_general_dilated_patches(
            a, (kh, kw), (sh, sw), pads, rhs_dilation=(dh, dw),
            dimension_numbers=jax.lax.conv_dimension_numbers(
                a.shape, (1, c, kh, kw), ("NCHW", "OIHW", "NCHW")))
        # patches: [N, C*kh*kw, oh, ow]
        return patches.reshape(n, patches.shape[1], -1)
    return apply("unfold", fwd, [x])


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def fwd(y, *p):
        k = y.shape[-1]
        if p:
            return (1 - epsilon) * y + epsilon * p[0]
        return (1 - epsilon) * y + epsilon / k
    ins = [label] + ([prior_dist] if prior_dist is not None else [])
    return apply("label_smooth", fwd, ins)


def _flash_eligible(query, key, attn_mask, dropout_p, training, is_causal):
    """Use the Pallas flash-attention kernel when the configuration maps onto
    it: TPU device, no explicit mask, no dropout, head_dim ≤ 128 and (causal
    or block-divisible keys) — AND the demotion gate agrees: under
    ``PADDLE_TPU_KERNELS=auto`` a measured A/B verdict (bench kernels leg /
    explicit ab_gate) at this or a nearby shape decides; with no verdict
    the incumbent-winner default keeps the kernel serving (a measured LOSS
    demotes it)."""
    from ...framework.flags import get_flags
    if not get_flags("FLAGS_use_flash_attention")["FLAGS_use_flash_attention"]:
        return False
    if attn_mask is not None or (dropout_p > 0 and training):
        return False
    if query.shape[-1] > 128 or query.ndim != 4:
        return False
    from ...ops.pallas import _common as _gate
    if not _gate.on_tpu():
        return False
    return _gate.pallas_default(
        "flash_attention", _gate.shape_sig(query, key), allow_nearest=True)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Reference: paddle.nn.functional.scaled_dot_product_attention
    (flash_attn kernel, phi/kernels/gpu/flash_attn_kernel.cu). Layout
    [batch, seq, heads, head_dim]. The Pallas flash-attention kernel
    (ops/pallas/flash_attention.py) backs the eligible cases; the XLA
    fused chain is the fallback."""
    if _flash_eligible(query, key, attn_mask, dropout_p, training, is_causal):
        from ...ops.pallas.flash_attention import flash_attention_bshd
        return apply("flash_attention",
                     lambda q, k, v: flash_attention_bshd(
                         q, k, v, causal=is_causal), [query, key, value])
    dk = _random.next_key() if (dropout_p > 0 and training) else None

    def fwd(q, k, v, *m):
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        # np.float32: under jax_enable_x64 a bare numpy float64 scalar
        # promotes the [B, H, S, S] scores, and everything after them, to
        # f64 — which the TPU emulates
        scale = np.float32(1.0 / np.sqrt(q.shape[-1]))
        # [B, S, H, D] -> [B, H, S, D]
        qt = jnp.swapaxes(qf, 1, 2)
        kt = jnp.swapaxes(kf, 1, 2)
        vt = jnp.swapaxes(v.astype(jnp.float32), 1, 2)
        scores = jnp.einsum("bhsd,bhtd->bhst", qt, kt) * scale
        if is_causal:
            s, t = scores.shape[-2], scores.shape[-1]
            causal = jnp.tril(jnp.ones((s, t), bool))
            scores = jnp.where(causal, scores, -1e30)
        if m:
            mask = m[0]
            if mask.dtype == jnp.bool_:
                scores = jnp.where(mask, scores, -1e30)
            else:
                scores = scores + mask.astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1)
        if dk is not None:
            keep = jax.random.bernoulli(dk, 1 - dropout_p, probs.shape)
            probs = jnp.where(keep, probs / (1 - dropout_p), 0.0)
        out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
        return jnp.swapaxes(out, 1, 2).astype(q.dtype)

    ins = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
    return apply("scaled_dot_product_attention", fwd, ins)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    """Reference: nn/functional/vision.py pixel_shuffle (phi
    pixel_shuffle_kernel): rearranges [N, C*r^2, H, W] -> [N, C, H*r, W*r].
    """
    r = int(upscale_factor)

    def f(a):
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 3, 1, 2))
        n, c, h, w = a.shape
        oc = c // (r * r)
        a = a.reshape(n, oc, r, r, h, w)
        a = jnp.transpose(a, (0, 1, 4, 2, 5, 3))
        a = a.reshape(n, oc, h * r, w * r)
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 2, 3, 1))
        return a

    return apply("pixel_shuffle", f, [x])


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = int(downscale_factor)

    def f(a):
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 3, 1, 2))
        n, c, h, w = a.shape
        a = a.reshape(n, c, h // r, r, w // r, r)
        a = jnp.transpose(a, (0, 1, 3, 5, 2, 4))
        a = a.reshape(n, c * r * r, h // r, w // r)
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 2, 3, 1))
        return a

    return apply("pixel_unshuffle", f, [x])


def affine_grid(theta, out_shape, align_corners=True, name=None):
    """Reference: nn/functional/vision.py affine_grid. theta [N, 2, 3];
    out_shape [N, C, H, W] -> grid [N, H, W, 2] (x, y in [-1, 1])."""
    N, C, H, W = [int(d) for d in out_shape]

    def f(th):
        if align_corners:
            xs = jnp.linspace(-1.0, 1.0, W)
            ys = jnp.linspace(-1.0, 1.0, H)
        else:
            xs = (jnp.arange(W) * 2 + 1) / W - 1.0
            ys = (jnp.arange(H) * 2 + 1) / H - 1.0
        gx, gy = jnp.meshgrid(xs, ys)              # [H, W]
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1)  # [H, W, 3]
        return jnp.einsum("hwk,nck->nhwc", base.astype(th.dtype), th)

    return apply("affine_grid", f, [theta])


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    """Reference: nn/functional/vision.py grid_sample (phi grid_sample).
    x [N, C, H, W]; grid [N, Ho, Wo, 2] normalized coords."""

    if mode not in ("bilinear", "nearest"):
        raise NotImplementedError(f"grid_sample mode {mode!r} "
                                  "(bilinear/nearest supported)")
    if padding_mode not in ("zeros", "border"):
        raise NotImplementedError(
            f"grid_sample padding_mode {padding_mode!r} "
            "(zeros/border supported)")

    def f(a, g):
        n, c, h, w = a.shape
        gx, gy = g[..., 0], g[..., 1]
        if align_corners:
            fx = (gx + 1.0) * (w - 1) / 2.0
            fy = (gy + 1.0) * (h - 1) / 2.0
        else:
            fx = ((gx + 1.0) * w - 1.0) / 2.0
            fy = ((gy + 1.0) * h - 1.0) / 2.0

        def gather(yy, xx):
            """a[n, :, yy, xx] with out-of-bounds handling -> [N,Ho,Wo,C]"""
            inside = ((xx >= 0) & (xx <= w - 1) & (yy >= 0)
                      & (yy <= h - 1))
            xc = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
            yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
            batch = jnp.arange(n)[:, None, None]
            vals = a[batch, :, yc, xc]             # [N, Ho, Wo, C]
            if padding_mode == "zeros":
                vals = jnp.where(inside[..., None], vals, 0.0)
            return vals

        if mode == "nearest":
            out = gather(jnp.round(fy), jnp.round(fx))
        else:  # bilinear
            x0, y0 = jnp.floor(fx), jnp.floor(fy)
            x1, y1 = x0 + 1, y0 + 1
            wa = (x1 - fx) * (y1 - fy)
            wb = (fx - x0) * (y1 - fy)
            wc = (x1 - fx) * (fy - y0)
            wd = (fx - x0) * (fy - y0)
            out = (gather(y0, x0) * wa[..., None]
                   + gather(y0, x1) * wb[..., None]
                   + gather(y1, x0) * wc[..., None]
                   + gather(y1, x1) * wd[..., None])
        return jnp.transpose(out, (0, 3, 1, 2))    # [N, C, Ho, Wo]

    return apply("grid_sample", f, [x, grid])


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """col2im, the inverse of unfold (reference: F.fold over phi
    fold_kernel). x: [N, C*kh*kw, L] -> [N, C, out_h, out_w]; overlapping
    patch contributions accumulate (one scatter-add, like istft's WOLA)."""
    oh_, ow_ = (output_sizes, output_sizes) if isinstance(
        output_sizes, int) else output_sizes
    kh, kw = (kernel_sizes, kernel_sizes) if isinstance(kernel_sizes, int) \
        else kernel_sizes
    sh, sw = (strides, strides) if isinstance(strides, int) else strides
    ph, pw = (paddings, paddings) if isinstance(paddings, int) else paddings
    dh, dw = (dilations, dilations) if isinstance(dilations, int) \
        else dilations

    def fwd(a):
        n, ckk, L = a.shape
        c = ckk // (kh * kw)
        nh = (oh_ + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
        nw = (ow_ + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
        assert nh * nw == L, (f"L={L} inconsistent with output_sizes "
                              f"({nh}x{nw} patches expected)")
        a = a.reshape(n, c, kh, kw, nh, nw)
        # padded-canvas positions of every (patch, offset) sample
        py = (jnp.arange(nh)[:, None] * sh
              + jnp.arange(kh)[None, :] * dh)     # [nh, kh]
        px = (jnp.arange(nw)[:, None] * sw
              + jnp.arange(kw)[None, :] * dw)     # [nw, kw]
        Hp, Wp = oh_ + 2 * ph, ow_ + 2 * pw
        flat_pos = (py[:, :, None, None] * Wp
                    + px[None, None, :, :])       # [nh, kh, nw, kw]
        vals = jnp.transpose(a, (0, 1, 4, 2, 5, 3))  # [n, c, nh, kh, nw, kw]
        out = jnp.zeros((n, c, Hp * Wp), a.dtype).at[
            :, :, flat_pos.reshape(-1)].add(
            vals.reshape(n, c, -1))
        out = out.reshape(n, c, Hp, Wp)
        return out[:, :, ph:ph + oh_, pw:pw + ow_]

    return apply("fold", fwd, [x])


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW",
                   name=None):
    """TSM temporal shift (reference: F.temporal_shift over phi
    temporal_shift_kernel): shift the first shift_ratio channels one step
    back in time, the next block one step forward; zero-pad the ends."""

    if shift_ratio > 0.5:
        raise ValueError(
            f"temporal_shift shift_ratio ({shift_ratio}) must be <= 0.5 "
            "(back + forward shifted blocks cannot exceed the channels)")

    def fwd(a):
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 3, 1, 2))
        nt, c, h, w = a.shape
        n = nt // seg_num
        a = a.reshape(n, seg_num, c, h, w)
        c1 = int(c * shift_ratio)
        c2 = int(c * 2 * shift_ratio)
        back = jnp.concatenate(
            [a[:, 1:, :c1], jnp.zeros_like(a[:, :1, :c1])], axis=1)
        fwd_ = jnp.concatenate(
            [jnp.zeros_like(a[:, :1, c1:c2]), a[:, :-1, c1:c2]], axis=1)
        out = jnp.concatenate([back, fwd_, a[:, :, c2:]], axis=2)
        out = out.reshape(nt, c, h, w)
        if data_format == "NHWC":
            out = jnp.transpose(out, (0, 2, 3, 1))
        return out

    return apply("temporal_shift", fwd, [x])
