"""Neural-network ops.

Reference: src/operator/nn/ — convolution.cc (ConvolutionParam),
fully_connected.cc, pooling.cc, batch_norm.cc, layer_norm.cc, dropout-inl.h,
softmax.cc, activation.cc, leaky_relu.cc; cuDNN paths in
src/operator/nn/cudnn/.

TPU-native: conv → `lax.conv_general_dilated` (MXU-tiled by XLA, replacing
cuDNN algo selection); pooling → `lax.reduce_window`; norms/softmax →
jnp compositions that XLA fuses into the surrounding matmuls.  MXNet layout
convention (NCHW / NCW / NCDHW) is preserved at the API; XLA relayouts
internally for the MXU so no NHWC surface change is needed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


def _tup(v, n):
    if v is None:
        return (0,) * n
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    return v if len(v) == n else v + v[-1:] * (n - len(v))


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------


@register("FullyConnected", aliases=["fully_connected"])
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                     flatten=True):
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    # weight layout: (num_hidden, in_units) — reference keeps cuBLAS row-major
    out = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution — MXNet NCHW layout; kernel layout OIHW
# ---------------------------------------------------------------------------

_CONV_DIMS = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
              3: ("NCDHW", "OIDHW", "NCDHW")}


@register("Convolution", aliases=["convolution"])
def _convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, no_bias=False,
                 cudnn_tune=None, cudnn_off=False, workspace=1024, layout=None):
    n = len(kernel)
    stride = _tup(stride or 1, n)
    dilate = _tup(dilate or 1, n)
    pad = _tup(pad, n)
    spatial = "DHW"[-n:] if n != 2 else "HW"
    lhs_spec = "NC" + spatial
    rhs_spec = "OI" + spatial
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (lhs_spec, rhs_spec, lhs_spec))
    # no preferred_element_type: its transpose rule rejects the mixed
    # fp32-cotangent/bf16-operand combo under grad, and TPU bf16 convs
    # already accumulate in fp32 on the MXU
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    out = out.astype(data.dtype)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


@register("Deconvolution", aliases=["deconvolution"])
def _deconvolution(data, weight, bias=None, kernel=None, stride=None,
                   dilate=None, pad=None, adj=None, num_filter=None,
                   num_group=1, no_bias=True, target_shape=None,
                   cudnn_tune=None, cudnn_off=False, workspace=1024, layout=None):
    n = len(kernel)
    stride = _tup(stride or 1, n)
    dilate = _tup(dilate or 1, n)
    pad = _tup(pad, n)
    adj = _tup(adj or 0, n)
    spatial = "DHW"[-n:] if n != 2 else "HW"
    lhs_spec = "NC" + spatial
    # weight layout for Deconvolution is (in, out/g, *kernel) = IOHW
    rhs_spec = "IO" + spatial
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (lhs_spec, rhs_spec, lhs_spec))
    # transposed conv: pad by effective-kernel-1 minus user pad, and run
    # the SPATIALLY FLIPPED kernel — Deconvolution is the transpose of
    # correlation, which this dilated-conv emulation only reproduces with
    # the flip (caught by the torch-oracle parity lane)
    weight = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
    if num_group > 1:
        # (in, out/g, *k) -> (in/g, out, *k): lax feature_group_count
        # wants per-group input channels and GROUP-MAJOR output channels
        cin, og = weight.shape[0], weight.shape[1]
        w = weight.reshape((num_group, cin // num_group, og)
                           + tuple(kernel))
        weight = jnp.moveaxis(w, 0, 1).reshape(
            (cin // num_group, og * num_group) + tuple(kernel))
    eff = [(k - 1) * d + 1 for k, d in zip(kernel, dilate)]
    pads = [(e - 1 - p, e - 1 - p + a) for e, p, a in zip(eff, pad, adj)]
    out = lax.conv_general_dilated(
        data, weight, window_strides=(1,) * n, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------


@register("Pooling", aliases=["pooling"])
def _pooling(data, kernel=None, pool_type="max", global_pool=False,
             stride=None, pad=None, pooling_convention="valid",
             count_include_pad=True, cudnn_off=False, layout=None, p_value=2):
    n = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _tup(kernel, n)
    stride = _tup(stride or kernel, n)
    pad = _tup(pad, n)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad right enough that ceil division is covered
        pads = [(0, 0), (0, 0)]
        for i in range(n):
            in_sz = data.shape[2 + i]
            out_sz = -(-(in_sz + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz
            pads.append((pad[i], max(needed - pad[i], pad[i])))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return summed / counts
    if pool_type == "lp":
        powed = lax.reduce_window(jnp.abs(data) ** p_value, 0.0, lax.add,
                                  window, strides, pads)
        return powed ** (1.0 / p_value)
    raise ValueError("bad pool_type %r" % pool_type)


@register("AdaptiveAvgPooling2D", aliases=["_contrib_AdaptiveAvgPooling2D"])
def _adaptive_avg_pool(data, output_size=1):
    os = _tup(output_size, 2)
    n, c, h, w = data.shape
    # reduce via mean over equal bins (exact when divisible; BASELINE nets are)
    x = data.reshape(n, c, os[0], h // os[0], os[1], w // os[1])
    return jnp.mean(x, axis=(3, 5))


@register("BilinearResize2D", aliases=["_contrib_BilinearResize2D"])
def _bilinear_resize(data, height=None, width=None, scale_height=None,
                     scale_width=None, mode="size"):
    n, c, h, w = data.shape
    th = height or int(h * scale_height)
    tw = width or int(w * scale_width)
    return jax.image.resize(data, (n, c, th, tw), method="linear")


@register("UpSampling")
def _upsampling(data, scale=2, sample_type="nearest", num_args=1):
    n, c, h, w = data.shape
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="linear")


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


@register("Activation", aliases=["activation"])
def _activation(data, act_type="relu"):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "log_sigmoid":
        return jax.nn.log_sigmoid(data)
    if act_type == "mish":
        return data * jnp.tanh(jax.nn.softplus(data))
    raise ValueError("bad act_type %r" % act_type)


@register("LeakyReLU", aliases=["leaky_relu", "_npx_leaky_relu"])
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if data.ndim > 2 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * (jnp.exp(data) - 1.0))
    if act_type == "selu":
        alpha, lam = 1.6732632423543772, 1.0507009873554805
        return lam * jnp.where(data >= 0, data, alpha * (jnp.exp(data) - 1.0))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(data, approximate=True)
    if act_type == "rrelu":  # eval-mode deterministic
        return jnp.where(data >= 0, data, data * (lower_bound + upper_bound) / 2)
    raise ValueError("bad act_type %r" % act_type)


@register("softmax", aliases=["Softmax"])
def _softmax(data, axis=-1, temperature=None, length=None, use_length=False,
             dtype=None):
    x = data if temperature in (None, 1.0) else data / temperature
    if use_length and length is not None:
        steps = jnp.arange(x.shape[axis])
        bshape = [1] * x.ndim
        bshape[axis] = x.shape[axis]
        mask = steps.reshape(bshape) < length.reshape(
            [x.shape[0]] + [1] * (x.ndim - 1))
        x = jnp.where(mask, x, -jnp.inf)
    out = jax.nn.softmax(x, axis=axis)
    if use_length and length is not None:
        out = jnp.where(jnp.isnan(out), 0.0, out)
    return out.astype(dtype or data.dtype)


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, dtype=None):
    x = data if temperature in (None, 1.0) else data / temperature
    return jax.nn.log_softmax(x, axis=axis).astype(dtype or data.dtype)


@register("sparse_softmax_cross_entropy")
def _sparse_softmax_cross_entropy(data, label, axis=-1):
    """``-log softmax(data)[label]`` along `axis`, in float32: the
    logsumexp less the picked logit, `label` clipped into the classes as
    `pick` clips it.  The pick is a compare, a select and a sum, no
    gather: XLA fuses the float32 copy of the logits into the two
    reductions and never writes it (a gather's operand is written whole
    first, a float32 ``[..., classes]`` tensor read for one value a
    row), and the select's transpose is a select, so the backward,
    ``softmax - onehot``, holds no scatter either."""
    x = data.astype(jnp.float32)
    axis = axis % x.ndim
    idx = jnp.clip(label.astype(jnp.int32), 0, x.shape[axis] - 1)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    hit = lax.broadcasted_iota(jnp.int32, shape, axis) \
        == jnp.expand_dims(idx, axis)
    picked = jnp.sum(jnp.where(hit, x, 0.0), axis=axis)
    return jax.nn.logsumexp(x, axis=axis) - picked


@register("softmin")
def _softmin(data, axis=-1, temperature=None, dtype=None):
    return _softmax(-data, axis=axis, temperature=temperature, dtype=dtype)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    onehot = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1],
                            dtype=logp.dtype)
    return -jnp.sum(logp * onehot)


@register("SoftmaxOutput", aliases=["softmax_output"])
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    # forward is plain softmax; the custom grad (p - onehot) comes out of the
    # VJP of cross-entropy at the Gluon/Module loss level.
    return jax.nn.softmax(data, axis=1 if multi_output else -1)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


@register("BatchNorm", aliases=["batch_norm"], num_outputs=3,
          aux_writeback={1: 3, 2: 4})
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                min_calib_range=None, max_calib_range=None, training=True):
    if output_mean_var:
        raise NotImplementedError("BatchNorm(output_mean_var=True)")
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    bshape = [1] * data.ndim
    bshape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if training and not use_global_stats:
        # both statistics from one read of the data: float32 means of d
        # and d² for d = x - K, K the moving mean (a constant here), so
        # the two reductions share their operand and XLA folds both into
        # the producer's output fusion (jnp.var needs the mean first: a
        # second full pass).  K bounds the cancellation of E[d²] - E[d]²
        # once the moving mean tracks the batch's; at K = 0 it is the
        # plain one-pass form of _contrib_SyncBatchNorm.
        k = lax.stop_gradient(moving_mean).astype(jnp.float32)
        d = data.astype(jnp.float32) - k.reshape(bshape)
        m1 = jnp.mean(d, axis=red)
        mean = k + m1
        var = jnp.maximum(jnp.mean(jnp.square(d), axis=red) - m1 * m1, 0.0)
        new_mean = momentum * moving_mean + (1.0 - momentum) * mean
        new_var = momentum * moving_var + (1.0 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape).astype(data.dtype)) * \
        (inv * g.astype(data.dtype)).reshape(bshape) + \
        beta.astype(data.dtype).reshape(bshape)
    return out, new_mean, new_var


@register("LayerNorm", aliases=["layer_norm"])
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.var(x32, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    norm = ((x32 - mean) * inv).astype(data.dtype)
    ax = axis % data.ndim
    bshape = [1] * data.ndim
    bshape[ax] = data.shape[ax]
    return norm * gamma.reshape(bshape) + beta.reshape(bshape)


@register("GroupNorm")
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    n, c = data.shape[:2]
    rest = data.shape[2:]
    x = data.reshape(n, num_groups, c // num_groups, *rest).astype(jnp.float32)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    norm = ((x - mean) * lax.rsqrt(var + eps)).reshape(data.shape).astype(data.dtype)
    bshape = (1, c) + (1,) * len(rest)
    return norm * gamma.reshape(bshape) + beta.reshape(bshape)


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=red, keepdims=True)
    var = jnp.var(x32, axis=red, keepdims=True)
    norm = ((x32 - mean) * lax.rsqrt(var + eps)).astype(data.dtype)
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return norm * gamma.reshape(bshape) + beta.reshape(bshape)


@register("RMSNorm")
def _rms_norm(data, gamma, axis=-1, eps=1e-6):
    """``data / sqrt(mean(data^2) + eps) * gamma``, the mean in float32.
    The result has `data`'s dtype whatever dtype `gamma` is kept in."""
    x32 = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=axis, keepdims=True)
    norm = x32 * lax.rsqrt(ms + eps)
    if gamma.dtype == data.dtype:
        return norm.astype(data.dtype) * gamma
    # a gain kept in another dtype (Mamba2Mixer's stays float32 in a cast
    # net) is applied in float32, before the one rounding, and promotes
    # nothing downstream
    return (norm * gamma.astype(jnp.float32)).astype(data.dtype)


# ---------------------------------------------------------------------------
# Dropout — takes an RNG key array as first input (plumbed by nd wrapper)
# ---------------------------------------------------------------------------


@register("Dropout", aliases=["dropout"], needs_rng=True)
def _dropout(key, data, p=0.5, mode="training", axes=(), cudnn_off=False,
             training=True):
    if not training or p <= 0.0:
        return data
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ---------------------------------------------------------------------------
# Attention (reference: src/operator/contrib/transformer.cc interleaved
# self-attention ops).  ops/attention.py decides what runs: the Pallas
# flash kernels straight on these (B, T, H*D) tensors where its rule
# holds, the jnp composition on split-and-transposed heads otherwise.
# ---------------------------------------------------------------------------


@register("multi_head_attention")
def _mha(q, k, v, mask=None, num_heads=1, scaled=True, causal=False,
         units=None,  # units: carried for ONNX export (scale = sqrt(units/heads))
         window=None):
    # q,k,v: (B, T, H*D), mask broadcastable to (B, H, Tq, Tk); window: a
    # causal call's band, the `window` keys that end with the query's own
    from .attention import attention_heads
    return attention_heads(q, k, v, num_heads,
                           scale=None if scaled else 1.0,  # None: 1/sqrt(D)
                           causal=causal, mask=mask, window=window)


def yarn_inv_freq(rotary_dim, theta, factor, original, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's blended inverse frequencies (Peng et al. 2023, the
    "NTK-by-parts" rule) of a `rotary_dim`-lane rotary part, float64
    numpy: ``f_i = theta^(-2i/d)`` kept where a lane turns more than
    `beta_fast` times over the `original` context, divided by `factor`
    where it turns less than `beta_slow` times, blended linearly in
    between."""
    import numpy as np
    d = rotary_dim
    f = float(theta) ** (-np.arange(d // 2, dtype=np.float64) * 2.0 / d)

    def lane(turns):
        return d * math.log(original / (2.0 * math.pi * turns)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(lane(beta_fast)), 0)
    high = min(math.ceil(lane(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (1.0 - ramp) * f + ramp * f / factor


@register("rotary_embedding")
def _rotary_embedding(data, num_heads=1, rotary_dim=None, theta=10000.0,
                      first=False, yarn=None, attention_factor=1.0):
    """Rotary positions on the packed (B, T, H*D) tensor a projection
    produces: the LAST `rotary_dim` lanes of every head are rotated by
    the position's angle, the first D - rotary_dim pass through (a head
    laid out [nope | rope], as latent attention has it); with `first` the
    FIRST `rotary_dim` lanes are and the rest pass through (a partial
    rotary factor).  Half-split pairs (lane i with lane i + rotary_dim/2,
    the NeoX layout), angles pos * theta^(-2i/rotary_dim) in float32,
    positions 0..T-1.  `yarn` = (factor, original positions, beta_fast,
    beta_slow) blends the frequencies (`yarn_inv_freq`);
    `attention_factor` multiplies cos and sin (YaRN's temperature, on q
    and k alike).  The turn is float32 and rounded once to the data's
    dtype.  Where `ops/rotary.py:rotary_rule` holds (heads of whole
    128-lane blocks, `T` in its row blocks, bf16 or float32) a TPU runs
    the same mathematics as one Pallas kernel on the packed layout,
    forward and backward; anything else the composition below."""
    from . import attention, rotary
    b, t, hd = data.shape
    d = hd // num_heads
    r = d if rotary_dim is None else rotary_dim
    if rotary.rotary_rule(t, d, r, data.dtype) and attention._on_tpu():
        rotary.count("kernel")
        return rotary.turn(data, num_heads, r, theta, first, yarn,
                           attention_factor)
    rotary.count("composition")
    half = r // 2
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / r))
    else:
        inv = jnp.asarray(yarn_inv_freq(r, theta, *yarn), jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x = data.reshape(b, t, num_heads, d)
    rope = (x[..., :r] if first else x[..., d - r:]).astype(jnp.float32)
    x1, x2 = rope[..., :half], rope[..., half:]
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(data.dtype)
    parts = [turned, x[..., r:]] if first else [x[..., :d - r], turned]
    return jnp.concatenate(parts, axis=-1).reshape(b, t, hd)


# ---------------------------------------------------------------------------
# Mixture of experts, token-choice top-k on this chip's share of the experts
# (parallel/moe.py holds the mathematics; gluon.nn.TokenChoiceMoE the block).
# ---------------------------------------------------------------------------


@register("moe_token_choice", num_outputs=4,
          aux_writeback={1: 5, 2: 6, 3: 7})
def _moe_token_choice(data, router_weight, router_correction,
                      gate_up_weight, down_weight, assignments, elsewhere,
                      buffer_calls, expert_input=None, held=(0,),
                      top_k=1, scale=1.0, norm_topk_prob=True, count=False,
                      activation="swiglu"):
    """The held experts' part of a token-choice layer for (..., d) tokens.
    `assignments` (H,), `elsewhere` (1,) and `buffer_calls` (2,) are
    float32 counters written in place (aux state, as BatchNorm's moving
    statistics): with `count` they grow by this call's assignments a held
    expert, by those routed to experts held elsewhere, and by (1 where
    the load took the exact no-drop buffer and not the short one, 1 for
    the call).  `gate_up_weight` is the experts' first matrix
    (`activation` "relu2": no gate in it); `expert_input`, where given, is
    what the experts read in place of `data` (a latent)."""
    from ..parallel.moe import token_choice_moe
    y, here, away, exact = token_choice_moe(
        data, router_weight, router_correction, gate_up_weight,
        down_weight, held=tuple(held), top_k=top_k, scale=scale,
        norm_topk_prob=norm_topk_prob, activation=activation,
        expert_input=expert_input)
    if count:
        assignments = assignments + lax.stop_gradient(here)
        elsewhere = elsewhere + lax.stop_gradient(away)
        buffer_calls = buffer_calls + jnp.stack(
            [lax.stop_gradient(exact), jnp.ones((), exact.dtype)])
    return y, assignments, elsewhere, buffer_calls


@register("moe_topk_choice", differentiable=False)
def _moe_topk_choice(data, router_weight, router_correction, top_k=1):
    """The experts (global ids, (..., k) int32) `moe_token_choice` picks."""
    from ..parallel.moe import topk_choice
    idx, _ = topk_choice(data.reshape(-1, data.shape[-1]), router_weight,
                         router_correction, top_k)
    return idx.reshape(data.shape[:-1] + (top_k,))


# ---------------------------------------------------------------------------
# CTC loss (reference: src/operator/nn/ctc_loss.cc — warp-ctc/cuDNN CTC).
# TPU-native: the alpha (forward-variable) recursion is a lax.scan over time
# with log-sum-exp accumulation — static shapes, differentiable by autodiff,
# no cuDNN dependency.  Blank label index 0 (MXNet blank_label='first').
# ---------------------------------------------------------------------------


@register("CTCLoss", aliases=["ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"])
def _ctc_loss(pred, label, data_lengths=None, label_lengths=None,
              blank_label="first"):
    """pred: (T, N, C) activations (softmax applied internally, like the
    reference).  blank_label='first': blank = class 0, labels 1..C-1,
    0-padded.  blank_label='last': blank = class C-1, labels 0..C-2,
    padded with -1 — remapped onto the 'first' layout by rolling the class
    axis so one recursion serves both."""
    T, N, C = pred.shape
    L = label.shape[1]
    S = 2 * L + 1
    logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
    label = label.astype(jnp.int32)
    if blank_label == "last":
        # move blank channel C-1 to the front and shift labels up by one
        logp = jnp.concatenate([logp[..., -1:], logp[..., :-1]], axis=-1)
        if label_lengths is None:
            lab_len = jnp.sum((label >= 0).astype(jnp.int32), axis=1)
        else:
            lab_len = label_lengths.astype(jnp.int32)
        label = jnp.where(label >= 0, label + 1, 0)
    elif label_lengths is None:
        # infer: count of non-zero entries (0 is blank ⇒ cannot be a label)
        lab_len = jnp.sum((label != 0).astype(jnp.int32), axis=1)
    else:
        lab_len = label_lengths.astype(jnp.int32)
    if data_lengths is None:
        seq_len = jnp.full((N,), T, jnp.int32)
    else:
        seq_len = data_lengths.astype(jnp.int32)

    # extended sequence: blank, l1, blank, l2, ..., blank  → shape (N, S)
    ext = jnp.zeros((N, S), jnp.int32)
    ext = ext.at[:, 1::2].set(label)
    # transition-2 allowed where ext[s] != blank and ext[s] != ext[s-2]
    ext_shift2 = jnp.pad(ext[:, :-2], ((0, 0), (2, 0)), constant_values=-1)
    allow2 = (ext != 0) & (ext != ext_shift2)          # (N, S)

    neg_inf = jnp.float32(-1e30)
    pos = jnp.arange(S)
    alpha0 = jnp.where(pos[None, :] < 2,
                       jnp.take_along_axis(logp[0], ext, axis=-1), neg_inf)
    alpha0 = jnp.where((pos[None, :] == 1) & (lab_len[:, None] == 0),
                       neg_inf, alpha0)

    def step(alpha, t):
        a1 = jnp.pad(alpha[:, :-1], ((0, 0), (1, 0)), constant_values=neg_inf)
        a2 = jnp.pad(alpha[:, :-2], ((0, 0), (2, 0)), constant_values=neg_inf)
        a2 = jnp.where(allow2, a2, neg_inf)
        m = jnp.maximum(jnp.maximum(alpha, a1), a2)
        new = m + jnp.log(jnp.exp(alpha - m) + jnp.exp(a1 - m) +
                          jnp.exp(a2 - m))
        new = new + jnp.take_along_axis(logp[t], ext, axis=-1)
        # past each sequence's length the alphas freeze
        new = jnp.where((t < seq_len)[:, None], new, alpha)
        return new, None

    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    # final: logsumexp of positions 2*lab_len and 2*lab_len - 1
    last = 2 * lab_len
    a_last = jnp.take_along_axis(alpha, last[:, None], axis=1)[:, 0]
    a_prev = jnp.take_along_axis(alpha, jnp.maximum(last - 1, 0)[:, None],
                                 axis=1)[:, 0]
    a_prev = jnp.where(lab_len > 0, a_prev, neg_inf)
    m = jnp.maximum(a_last, a_prev)
    ll = m + jnp.log(jnp.exp(a_last - m) + jnp.exp(a_prev - m))
    return -ll


@register("LinearRegressionOutput", aliases=["linear_regression_output"])
def _linear_regression_output(data, label, grad_scale=1.0):
    # forward is identity; Module.backward injects the implicit l2 loss
    # gradient (pred - label) the reference computes in-op
    # (src/operator/regression_output.cc)
    return data


@register("MAERegressionOutput", aliases=["mae_regression_output"])
def _mae_regression_output(data, label, grad_scale=1.0):
    return data


@register("LogisticRegressionOutput", aliases=["logistic_regression_output"])
def _logistic_regression_output(data, label, grad_scale=1.0):
    return jax.nn.sigmoid(data)
