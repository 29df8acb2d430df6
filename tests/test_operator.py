"""Per-operator battery: numpy-reference forward + numeric-gradient check
for EVERY registered op.

Reference: tests/python/unittest/test_operator.py (~10k lines of per-op
numpy-reference + check_numeric_gradient tests) — rebuilt as a spec table
(`SPECS`) driving three parametrized tests:

  test_forward   — invoke the op, compare against a NumPy reference (when
                   given) or assert shape/finiteness sanity,
  test_grad      — central-difference gradient check via
                   test_utils.check_numeric_gradient for differentiable ops,
  test_coverage  — every unique registry op must appear in SPECS or in
                   TESTED_ELSEWHERE (pointing at the suite that covers it);
                   adding an op without a test fails CI.

Reference coverage: ~85% of SPECS carry a `ref=` numpy re-implementation.
The ~99 specs WITHOUT refs are exactly these classes, exempt by nature:
  * stochastic samplers (_random_* / _sample_* / _npi_<dist> / shuffle /
    *_like / _image_random_*) — no deterministic reference exists;
    shape+finiteness here, moment checks in their dedicated tests;
  * _npi_partition/_npi_argpartition — within-segment order is
    UNSPECIFIED; pinned by test_npi_partition_semantics instead;
  * _npi_empty_like — values are undefined by contract;
  * decode/IO ops (_cvimread/_cvimdecode/_image_imdecode) and resamplers
    (_cvimresize/_image_resize/BilinearResize2D/BilinearSampler/
    GridGenerator/SpatialTransformer/Correlation/Deconvolution/ROIPooling
    /PSROIPooling family) — pinned by exactness-anchor tests further down
    this file (test_deformable_matches_convolution, PSROI/box anchors) and
    tests/test_ssd.py end-to-end parity rather than elementwise refs;
  * detection pipeline ops (MultiBox*/Proposal*/box_nms/box_encode/
    mrcnn_mask_target) — protocol-level checks live in test_ssd.py and the
    box-anchor tests here;
  * quantized/intgemm kernels — numeric contracts pinned in
    tests/test_quantization.py;
  * linalg factorizations (linalg_syevd/gelqf/maketrian) — eigenvector/
    factor sign+order ambiguity; validated by reconstruction identities in
    their grad specs and tests/test_ndarray.py linalg checks;
  * im2col/col2im, count_sketch, hawkesll, calibrate_entropy,
    sldwin_atten_* — pinned by dedicated reference tests in this file
    (sliding-window attention vs dense mask, hawkesll vs slow loop,
    KL-calibration behaviour) rather than one-liner refs.
"""
import numpy as np
import pytest
import scipy.special as _sp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ndarray.ndarray import invoke
from mxnet_tpu.ops import registry
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

R = np.random.RandomState(7)


def f(*shape):
    """Well-conditioned float input away from singular points."""
    return (R.uniform(0.3, 0.9, shape) * R.choice([-1.0, 1.0], shape)
            ).astype(np.float32)


def fpos(*shape):
    return R.uniform(0.3, 0.9, shape).astype(np.float32)


def funit(*shape):
    return R.uniform(-0.7, 0.7, shape).astype(np.float32)


def ints(*shape, lo=0, hi=8):
    return R.randint(lo, hi, shape).astype(np.int32)


def sep(*shape):
    """Well-separated values: numeric grad safe at order statistics."""
    flat = np.argsort(R.rand(int(np.prod(shape))))
    return (flat.reshape(shape).astype(np.float32)
            + R.uniform(0.1, 0.3, shape).astype(np.float32))


class Spec:
    def __init__(self, inputs, params=None, ref=None, grad=None, rtol=1e-4,
                 atol=1e-4, grad_rtol=1e-2, grad_atol=1e-2):
        self.inputs = inputs          # callable -> list[np.ndarray]
        self.params = params or {}
        self.ref = ref                # callable(*np_inputs) -> np / tuple
        self.grad = grad              # None = infer from registry
        self.rtol, self.atol = rtol, atol
        self.grad_rtol, self.grad_atol = grad_rtol, grad_atol


def S(inputs, params=None, ref=None, **kw):
    return Spec(inputs, params, ref, **kw)


def _own_draw(*shapes):
    """Inputs from a generator of their own: the battery's shared one
    hands every later spec other numbers when a spec is added."""
    rng = np.random.RandomState(33)
    return [rng.uniform(-1, 1, shape).astype(np.float32) for shape in shapes]


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _ssm_scan_ref(x, b, c, dt, z, dt_bias, a_log, d):
    """Mamba-2's scan position by position: 2 heads of 2 lanes, one
    group of 3 state lanes."""
    step = np.log1p(np.exp(dt + dt_bias))
    xh = x.reshape(1, 8, 2, 2)
    h = np.zeros((1, 2, 2, 3))
    out = np.zeros_like(xh)
    for t in range(8):
        h = np.exp(-np.exp(a_log) * step[:, t])[..., None, None] * h \
            + (step[:, t, :, None] * xh[:, t])[..., None] \
            * b[:, t, None, None, :]
        out[:, t] = (h * c[:, t, None, None, :]).sum(-1) \
            + d[:, None] * xh[:, t]
    return (out.reshape(1, 8, 4) * _silu(z)).astype(np.float32)


def _masked_softmax_ref(x, m):
    b = m.astype(bool)
    xm = np.where(b, x, -1e30)
    e = np.exp(xm - xm.max(-1, keepdims=True))
    out = e / e.sum(-1, keepdims=True)
    return np.where(b, out, 0.0).astype(np.float32)


def _masked_log_softmax_ref(x, m):
    b = m.astype(bool)
    xm = np.where(b, x, -1e30)
    out = xm - xm.max(-1, keepdims=True) - np.log(
        np.exp(xm - xm.max(-1, keepdims=True)).sum(-1, keepdims=True))
    return np.where(b, out, -np.inf).astype(np.float32)


def _scatter_nd_ref(data, idx, shape):
    out = np.zeros(shape, data.dtype)
    out[tuple(idx[i] for i in range(idx.shape[0]))] = data
    return out


def _index_add_ref(data, index, value):
    out = data.copy()
    np.add.at(out, index, value)
    return out


def _index_set_ref(data, index, value):
    out = data.copy()
    out[index] = value
    return out


def _seq_mask_ref(x, lens, value=0.0):
    out = x.copy()
    for b, L in enumerate(lens.astype(int)):
        out[L:, b] = value
    return out


def _pool_max_ref(x, k, s, ceil=False):
    N, C, H, W = x.shape
    if ceil:
        Ho = -((H - k) // -s) + 1
        Wo = -((W - k) // -s) + 1
    else:
        Ho, Wo = (H - k) // s + 1, (W - k) // s + 1
    out = np.zeros((N, C, Ho, Wo), x.dtype)
    for i in range(Ho):
        for j in range(Wo):
            out[:, :, i, j] = x[:, :, i * s:min(i * s + k, H),
                                j * s:min(j * s + k, W)].max((2, 3))
    return out


def _lrn_ref(x, nsize=3, alpha=1e-4, beta=0.75, k=2.0):
    sq = np.square(x)
    half = nsize // 2
    acc = np.zeros_like(sq)
    C = x.shape[1]
    for c in range(C):
        lo, hi = max(0, c - half), min(C, c + half + 1)
        acc[:, c] = sq[:, lo:hi].sum(1)
    return x / np.power(k + (alpha / nsize) * acc, beta)


def _boxes(n):
    """(n, 4) corner boxes with x1<x2, y1<y2."""
    lo = R.uniform(0.0, 0.5, (n, 2)).astype(np.float32)
    hi = lo + R.uniform(0.1, 0.5, (n, 2)).astype(np.float32)
    return np.concatenate([lo, hi], 1)


def _iou_ref(a, b):
    out = np.zeros((a.shape[0], b.shape[0]), np.float32)
    for i in range(a.shape[0]):
        for j in range(b.shape[0]):
            ix = max(0.0, min(a[i, 2], b[j, 2]) - max(a[i, 0], b[j, 0]))
            iy = max(0.0, min(a[i, 3], b[j, 3]) - max(a[i, 1], b[j, 1]))
            inter = ix * iy
            ua = ((a[i, 2] - a[i, 0]) * (a[i, 3] - a[i, 1])
                  + (b[j, 2] - b[j, 0]) * (b[j, 3] - b[j, 1]) - inter)
            out[i, j] = inter / ua if ua > 0 else 0.0
    return out


def _conv2d_ref(x, w, b, stride=1, pad=0):
    N, C, H, W = x.shape
    O, _C, kh, kw = w.shape
    if pad:
        x = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        H, W = H + 2 * pad, W + 2 * pad
    Ho, Wo = (H - kh) // stride + 1, (W - kw) // stride + 1
    out = np.zeros((N, O, Ho, Wo), np.float32)
    for i in range(Ho):
        for j in range(Wo):
            patch = x[:, :, i * stride:i * stride + kh,
                      j * stride:j * stride + kw]          # N,C,kh,kw
            out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return out + b.reshape(1, -1, 1, 1)


# --- unary elementwise with direct numpy refs ------------------------------
_UNARY = {
    "abs": (np.abs, f), "negative": (np.negative, f),
    "exp": (np.exp, f), "expm1": (np.expm1, f),
    "log": (np.log, fpos), "log10": (np.log10, fpos),
    "log1p": (np.log1p, fpos), "log2": (np.log2, fpos),
    "sqrt": (np.sqrt, fpos), "rsqrt": (lambda x: 1 / np.sqrt(x), fpos),
    "cbrt": (np.cbrt, fpos), "rcbrt": (lambda x: 1 / np.cbrt(x), fpos),
    "square": (np.square, f), "reciprocal": (np.reciprocal, f),
    "sin": (np.sin, f), "cos": (np.cos, f), "tan": (np.tan, funit),
    "arcsin": (np.arcsin, funit), "arccos": (np.arccos, funit),
    "arctan": (np.arctan, f),
    "sinh": (np.sinh, f), "cosh": (np.cosh, f), "tanh": (np.tanh, f),
    "arcsinh": (np.arcsinh, f), "arccosh": (lambda x: np.arccosh(1 + x), fpos),
    "arctanh": (np.arctanh, funit),
    "sign": (np.sign, f), "ceil": (np.ceil, f), "floor": (np.floor, f),
    "trunc": (np.trunc, f), "rint": (np.rint, f), "round": (np.round, f),
    "fix": (np.fix, f),
    "sigmoid": (lambda x: 1 / (1 + np.exp(-x)), f),
    "relu": (lambda x: np.maximum(x, 0), f),
    "softsign": (lambda x: x / (1 + np.abs(x)), f),
    "identity": (lambda x: x, f),
    "erf": (lambda x: _sp.erf(x), f), "erfc": (lambda x: _sp.erfc(x), f),
    "erfinv": (lambda x: _sp.erfinv(x), funit),
    "gamma": (lambda x: _sp.gamma(x), fpos),
    "gammaln": (lambda x: _sp.gammaln(x), fpos),
    "digamma": (lambda x: _sp.digamma(x), fpos),
    "radians": (np.radians, f), "degrees": (np.degrees, f),
    "sinc": (np.sinc, f), "i0": (lambda x: _sp.i0(x), fpos),
    "selu": (lambda x: 1.0507009873554805 * np.where(
        x > 0, x, 1.6732632423543772 * (np.exp(x) - 1)), f),
    "gelu": (lambda x: 0.5 * x * (1 + _sp.erf(x / np.sqrt(2.0))), f),
    "silu": (lambda x: x / (1 + np.exp(-x)), f),
    "mish": (lambda x: x * np.tanh(np.log1p(np.exp(x))), f),
    "elu": (lambda x: np.where(x > 0, x, np.exp(x) - 1), f),
    "softrelu": (lambda x: np.log1p(np.exp(x)), f),
    "log_sigmoid": (lambda x: -np.log1p(np.exp(-x)), f),
    "hard_sigmoid": (lambda x: np.clip(0.2 * x + 0.5, 0, 1), f),
    "hard_swish": (lambda x: x * np.clip(x + 3, 0, 6) / 6.0, f),
    "isnan": (np.isnan, f), "isinf": (np.isinf, f),
    "isfinite": (np.isfinite, f),
    "logical_not": (lambda x: np.logical_not(x).astype(np.float32), f),
    "zeros_like_op": (np.zeros_like, f), "ones_like_op": (np.ones_like, f),
    "atleast_1d": (np.atleast_1d, f), "atleast_2d": (np.atleast_2d, f),
    "atleast_3d": (np.atleast_3d, f),
    "nan_to_num": (np.nan_to_num, f),
}

# --- binary broadcast with numpy refs --------------------------------------
_BINARY = {
    "broadcast_add": np.add, "broadcast_sub": np.subtract,
    "broadcast_mul": np.multiply, "broadcast_div": np.divide,
    "broadcast_maximum": np.maximum, "broadcast_minimum": np.minimum,
    "broadcast_hypot": np.hypot, "hypot": np.hypot,


    "broadcast_equal": lambda a, b: (a == b).astype(np.float32),
    "broadcast_not_equal": lambda a, b: (a != b).astype(np.float32),
    "broadcast_greater": lambda a, b: (a > b).astype(np.float32),
    "broadcast_greater_equal": lambda a, b: (a >= b).astype(np.float32),
    "broadcast_lesser": lambda a, b: (a < b).astype(np.float32),
    "broadcast_lesser_equal": lambda a, b: (a <= b).astype(np.float32),
    "broadcast_logical_and": lambda a, b: np.logical_and(a, b).astype(np.float32),
    "broadcast_logical_or": lambda a, b: np.logical_or(a, b).astype(np.float32),
    "broadcast_logical_xor": lambda a, b: np.logical_xor(a, b).astype(np.float32),
    "arctan2": np.arctan2, "copysign": np.copysign,
    "logaddexp": np.logaddexp, "fmod": np.fmod, "nextafter": np.nextafter,
    "heaviside": np.heaviside, "ldexp": lambda a, b: a * np.exp2(b),
}

SPECS = {}
for _name, (_ref, _gen) in _UNARY.items():
    SPECS[_name] = S(lambda g=_gen: [g(3, 4)], ref=_ref)
for _name, _ref in _BINARY.items():
    SPECS[_name] = S(lambda: [f(3, 4), fpos(3, 4)], ref=_ref)

SPECS.update({
    "arccosh": S(lambda: [1.0 + fpos(3, 4)], ref=np.arccosh),
    "broadcast_mod": S(lambda: [f(3, 4), fpos(3, 4)], ref=np.mod,
                       grad=False),
    "broadcast_power": S(lambda: [fpos(3, 4), f(3, 4)], ref=np.power),
    "nextafter": S(lambda: [f(3, 4), fpos(3, 4)], ref=np.nextafter,
                   grad=False),
    "lerp": S(lambda: [f(3, 4), f(3, 4), fpos(3, 4)],
              ref=lambda a, b, w: a + w * (b - a)),
    # reductions
    "sum": S(lambda: [f(2, 3, 4)], {"axis": (0, 2)},
             ref=lambda x: x.sum(axis=(0, 2))),
    "mean": S(lambda: [f(2, 3, 4)], {"axis": 1}, ref=lambda x: x.mean(1)),
    "max": S(lambda: [f(3, 4)], {"axis": 1}, ref=lambda x: x.max(1)),
    "min": S(lambda: [f(3, 4)], {"axis": 0}, ref=lambda x: x.min(0)),
    "prod": S(lambda: [fpos(3, 4)], {"axis": 1}, ref=lambda x: x.prod(1)),
    "nansum": S(lambda: [f(3, 4)], ref=np.nansum),
    "nanprod": S(lambda: [fpos(3, 4)], ref=np.nanprod),
    "norm": S(lambda: [f(3, 4)], {"ord": 2},
              ref=lambda x: np.sqrt((x * x).sum())),
    "std": S(lambda: [f(3, 4)], {"axis": 1}, ref=lambda x: x.std(1)),
    "var": S(lambda: [f(3, 4)], {"axis": 1}, ref=lambda x: x.var(1)),
    # well-separated values: numeric grad is undefined at tied extrema
    "ptp": S(lambda: [np.argsort(R.rand(3, 4), 1).astype(np.float32)
                      + f(3, 4) * 0.1],
             {"axis": 1}, ref=lambda x: np.ptp(x, 1)),
    "median": S(lambda: [f(3, 5)], {"axis": 1},
                ref=lambda x: np.median(x, 1), grad=False),
    "quantile": S(lambda: [f(3, 5)], {"q": 0.5, "axis": 1},
                  ref=lambda x: np.quantile(x, 0.5, 1), grad=False),
    "percentile": S(lambda: [f(3, 5)], {"q": 30.0, "axis": 1},
                    ref=lambda x: np.percentile(x, 30.0, 1), grad=False),
    "average": S(lambda: [f(3, 4)], {"axis": 1}, ref=lambda x: x.mean(1)),
    "logsumexp": S(lambda: [f(3, 4)], {"axis": 1},
                   ref=lambda x: np.log(np.exp(x).sum(1))),
    "moments": S(lambda: [f(3, 4)], {"axes": (0, 1)},
                 ref=lambda x: (x.mean(), x.var())),
    "argmax": S(lambda: [f(3, 4)], {"axis": 1},
                ref=lambda x: x.argmax(1).astype(np.float32)),
    "argmin": S(lambda: [f(3, 4)], {"axis": 1},
                ref=lambda x: x.argmin(1).astype(np.float32)),
    "argmax_channel": S(lambda: [f(3, 4)],
                        ref=lambda x: x.argmax(1).astype(np.float32)),
    # softmax family
    "softmax": S(lambda: [f(3, 4)], {"axis": -1},
                 ref=lambda x: np.exp(x) / np.exp(x).sum(-1, keepdims=True)),
    "softmin": S(lambda: [f(3, 4)], {"axis": -1},
                 ref=lambda x: np.exp(-x) / np.exp(-x).sum(-1, keepdims=True)),
    "log_softmax": S(lambda: [f(3, 4)], {"axis": -1},
                     ref=lambda x: x - x.max(-1, keepdims=True) - np.log(
                         np.exp(x - x.max(-1, keepdims=True)).sum(
                             -1, keepdims=True))),
    "masked_softmax": S(
        # mask keeps column 0 live so no row is fully masked
        lambda: [f(3, 4),
                 np.concatenate([np.ones((3, 1), np.int32),
                                 ints(3, 3, lo=0, hi=2)], 1)],
        {"axis": -1}, grad=False, ref=_masked_softmax_ref),
    # all-ones mask here (battery finiteness gate rejects the -inf the op
    # yields at masked slots); partial-mask path pinned by
    # test_masked_log_softmax_partial
    "masked_log_softmax": S(lambda: [f(3, 4), np.ones((3, 4), np.int32)],
                            {"axis": -1}, grad=False,
                            ref=lambda x, m: x - x.max(-1, keepdims=True)
                            - np.log(np.exp(x - x.max(-1, keepdims=True))
                                     .sum(-1, keepdims=True))),
    "softmax_cross_entropy": S(
        lambda: [f(3, 4), ints(3, lo=0, hi=4)], grad=False,
        ref=lambda x, y: np.asarray(-(
            (x - x.max(-1, keepdims=True)
             - np.log(np.exp(x - x.max(-1, keepdims=True)).sum(
                 -1, keepdims=True)))[np.arange(3), y]).sum(),
            np.float32)),
    # labels past both ends are clipped into the classes, as `pick` does
    "sparse_softmax_cross_entropy": S(
        lambda: _own_draw((3, 4)) + [np.array([-2, 1, 9], np.int32)],
        ref=lambda x, y: np.log(np.exp(x).sum(-1))
        - x[np.arange(3), np.clip(y, 0, 3)]),
    "smooth_l1": S(lambda: [f(3, 4)], {"scalar": 1.0},
                   ref=lambda x: np.where(np.abs(x) < 1, 0.5 * x * x,
                                          np.abs(x) - 0.5)),
    # shape ops
    "reshape": S(lambda: [f(3, 4)], {"shape": (4, 3)},
                 ref=lambda x: x.reshape(4, 3)),
    "flatten": S(lambda: [f(2, 3, 4)], ref=lambda x: x.reshape(2, 12)),
    "transpose": S(lambda: [f(3, 4)], ref=lambda x: x.T),
    "swapaxes": S(lambda: [f(2, 3, 4)], {"dim1": 0, "dim2": 2},
                  ref=lambda x: x.swapaxes(0, 2)),
    "expand_dims": S(lambda: [f(3, 4)], {"axis": 1},
                     ref=lambda x: x[:, None, :]),
    "squeeze": S(lambda: [f(3, 1, 4)], {"axis": 1},
                 ref=lambda x: x.squeeze(1)),
    "broadcast_to": S(lambda: [f(1, 4)], {"shape": (3, 4)},
                      ref=lambda x: np.broadcast_to(x, (3, 4))),
    "broadcast_axis": S(lambda: [f(1, 4)], {"axis": 0, "size": 3},
                        ref=lambda x: np.broadcast_to(x, (3, 4))),
    "concat": S(lambda: [f(2, 3), f(2, 3)], {"dim": 1},
                ref=lambda a, b: np.concatenate([a, b], 1)),
    "stack": S(lambda: [f(2, 3), f(2, 3)], {"axis": 0},
               ref=lambda a, b: np.stack([a, b], 0)),
    "split": S(lambda: [f(4, 6)], {"num_outputs": 2, "axis": 1},
               ref=lambda x: tuple(np.split(x, 2, 1))),
    "split_v2": S(lambda: [f(4, 6)], {"indices": (2, 4), "axis": 1},
                  ref=lambda x: tuple(np.split(x, [2, 4], 1))),
    "slice": S(lambda: [f(4, 5)], {"begin": (1, 0), "end": (3, 4)},
               ref=lambda x: x[1:3, 0:4]),
    "slice_axis": S(lambda: [f(4, 5)], {"axis": 1, "begin": 1, "end": 4},
                    ref=lambda x: x[:, 1:4]),
    "slice_like": S(lambda: [f(4, 5), f(2, 3)],
                    ref=lambda a, b: a[:2, :3]),
    "tile": S(lambda: [f(2, 3)], {"reps": (2, 2)},
              ref=lambda x: np.tile(x, (2, 2))),
    "repeat": S(lambda: [f(2, 3)], {"repeats": 2, "axis": 1},
                ref=lambda x: np.repeat(x, 2, 1)),
    "pad": S(lambda: [f(1, 1, 3, 3)],
             {"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
             ref=lambda x: np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))),
    "flip": S(lambda: [f(3, 4)], {"axis": 1}, ref=lambda x: x[:, ::-1]),
    "roll": S(lambda: [f(3, 4)], {"shift": 1, "axis": 1},
              ref=lambda x: np.roll(x, 1, 1)),
    "rot90": S(lambda: [f(3, 4)], {"k": 1, "axes": (0, 1)},
               ref=lambda x: np.rot90(x)),
    "diag": S(lambda: [f(4, 4)], ref=np.diag),
    "diagonal": S(lambda: [f(3, 3)], ref=np.diagonal),
    "tril": S(lambda: [f(4, 4)], ref=np.tril),
    "triu": S(lambda: [f(4, 4)], ref=np.triu),
    "trace_op": S(lambda: [f(4, 4)], ref=np.trace),
    "space_to_depth": S(lambda: [f(1, 1, 4, 4)], {"block_size": 2},
                        grad=False,
                        ref=lambda x: x.reshape(1, 1, 2, 2, 2, 2)
                        .transpose(0, 3, 5, 1, 2, 4).reshape(1, 4, 2, 2)),
    "depth_to_space": S(lambda: [f(1, 4, 2, 2)], {"block_size": 2},
                        grad=False,
                        ref=lambda x: x.reshape(1, 2, 2, 1, 2, 2)
                        .transpose(0, 3, 4, 1, 5, 2).reshape(1, 1, 4, 4)),
    "reverse": S(lambda: [f(3, 4)], {"axis": (0, 1)},
                 ref=lambda x: x[::-1, ::-1]),
    "shape_array": S(lambda: [f(3, 4)],
                     ref=lambda x: np.array([3, 4], np.int64), grad=False),
    "size_array": S(lambda: [f(3, 4)],
                    ref=lambda x: np.array([12], np.int64), grad=False),
    "cast": S(lambda: [f(3, 4)], {"dtype": "float32"}, ref=lambda x: x),
    "amp_cast": S(lambda: [f(3, 4)], {"dtype": "float32"}, ref=lambda x: x),
    "clip": S(lambda: [f(3, 4)], {"a_min": -0.5, "a_max": 0.5},
              ref=lambda x: np.clip(x, -0.5, 0.5)),
    # matmul
    "dot": S(lambda: [f(3, 4), f(4, 5)], ref=np.dot),
    "batch_dot": S(lambda: [f(2, 3, 4), f(2, 4, 5)], ref=np.matmul),
    "kron": S(lambda: [f(2, 2), f(2, 2)], ref=np.kron),
    "cross": S(lambda: [f(3, 3), f(3, 3)], ref=np.cross),
    "einsum": S(lambda: [f(2, 3), f(3, 4)], {"subscripts": "ij,jk->ik"},
                ref=lambda a, b: np.einsum("ij,jk->ik", a, b)),
    "khatri_rao": S(lambda: [f(2, 3), f(4, 3)],
                    ref=lambda a, b: np.vstack(
                        [np.kron(a[:, k], b[:, k]) for k in range(3)]).T),
    # linalg
    "linalg_gemm": S(lambda: [f(3, 4), f(4, 5), f(3, 5)],
                     ref=lambda a, b, c: a @ b + c),
    "linalg_gemm2": S(lambda: [f(3, 4), f(4, 5)], ref=lambda a, b: a @ b),
    "linalg_syrk": S(lambda: [f(3, 4)], ref=lambda a: a @ a.T),
    "linalg_trmm": S(lambda: [f(3, 3), f(3, 4)],
                     ref=lambda a, b: np.tril(a) @ b),
    "linalg_potrf": S(lambda: [_spd(3)], ref=np.linalg.cholesky,
                      grad=False),
    "linalg_potri": S(lambda: [np.linalg.cholesky(_spd(3))],
                      ref=lambda l: np.linalg.inv(l @ l.T), grad=False,
                      rtol=1e-3, atol=1e-3),
    "linalg_trsm": S(lambda: [np.tril(fpos(3, 3)) + 2 * np.eye(3, dtype=np.float32), f(3, 4)],
                     ref=lambda a, b: np.linalg.solve(np.tril(a), b),
                     grad=False),
    "linalg_det": S(lambda: [_spd(3)], ref=np.linalg.det),
    "linalg_slogdet": S(lambda: [_spd(3)], ref=np.linalg.slogdet,
                        grad=False),
    "linalg_inverse": S(lambda: [_spd(3)], ref=np.linalg.inv,
                        rtol=1e-3, atol=1e-3),
    "linalg_sumlogdiag": S(lambda: [_spd(3)],
                           ref=lambda a: np.log(np.diag(a)).sum()),
    "linalg_makediag": S(lambda: [f(4)], ref=np.diag),
    "linalg_extractdiag": S(lambda: [f(4, 4)], ref=np.diag),
    "linalg_maketrian": S(lambda: [f(6)], grad=False),
    "linalg_extracttrian": S(lambda: [f(3, 3)],
                             ref=lambda a: a[np.tril_indices(3)],
                             grad=False),
    "linalg_gelqf": S(lambda: [f(3, 4)], grad=False),
    "linalg_syevd": S(lambda: [_spd(3)], grad=False),
    # indexing
    "take": S(lambda: [f(5, 3), ints(4, hi=5)],
              ref=lambda a, i: a[i], grad=False),
    "batch_take": S(lambda: [f(3, 4), ints(3, hi=4)],
                    ref=lambda a, i: a[np.arange(3), i], grad=False),
    "pick": S(lambda: [f(3, 4), ints(3, hi=4)], {"axis": 1},
              ref=lambda a, i: a[np.arange(3), i], grad=False),
    "one_hot": S(lambda: [ints(4, hi=5)], {"depth": 5},
                 ref=lambda i: np.eye(5, dtype=np.float32)[i], grad=False),
    "gather_nd": S(lambda: [f(4, 5), np.array([[0, 1], [2, 3]], np.int32)],
                   ref=lambda a, i: a[i[0], i[1]], grad=False),
    "scatter_nd": S(lambda: [f(2), np.array([[0, 1], [2, 3]], np.int32)],
                    {"shape": (4, 5)}, grad=False,
                    ref=lambda d, i: _scatter_nd_ref(d, i, (4, 5))),
    "where_op": S(lambda: [ints(3, 4, lo=0, hi=2), f(3, 4), f(3, 4)],
                  ref=lambda c, a, b: np.where(c, a, b), grad=False),
    "where": S(lambda: [ints(3, 4, lo=0, hi=2), f(3, 4), f(3, 4)],
               ref=lambda c, a, b: np.where(c, a, b), grad=False),
    "boolean_mask": S(lambda: [f(4, 3), np.array([1, 0, 1, 1], np.int32)],
                      grad=False,
                      ref=lambda d, m: d[m.astype(bool)]),
    "index_add": S(lambda: [f(5, 3), np.array([1, 3], np.int32), f(2, 3)],
                   grad=False, ref=_index_add_ref),
    "index_copy": S(lambda: [f(5, 3), np.array([1, 3], np.int32), f(2, 3)],
                    grad=False, ref=_index_set_ref),
    "index_update": S(lambda: [f(5, 3), np.array([1, 3], np.int32),
                               f(2, 3)], grad=False, ref=_index_set_ref),
    "ravel_multi_index": S(
        lambda: [np.array([[1, 2], [0, 3]], np.int64)], {"shape": (3, 4)},
        ref=lambda d: np.ravel_multi_index((d[0], d[1]), (3, 4)),
        grad=False),
    "unravel_index": S(
        lambda: [np.array([5, 11], np.int64)], {"shape": (3, 4)},
        ref=lambda d: np.stack(np.unravel_index(d, (3, 4))), grad=False),
    "searchsorted": S(lambda: [np.sort(f(8)), f(3)], grad=False,
                      ref=np.searchsorted),
    "bincount": S(lambda: [ints(10, hi=5)], {"minlength": 5},
                  ref=lambda d: np.bincount(d, minlength=5), grad=False),
    "digitize": S(lambda: [f(5), np.sort(f(4))], grad=False,
                  ref=np.digitize),
    "histogram": S(lambda: [fpos(20)], {"bin_cnt": 5, "range": (0.0, 1.0)},
                   grad=False,
                   ref=lambda x: np.histogram(x, 5, (0.0, 1.0))),
    "interp": S(lambda: [f(4), np.sort(fpos(5)), fpos(5)], grad=False,
                ref=np.interp),
    # sorting
    "sort": S(lambda: [f(3, 6)], {"axis": -1}, ref=lambda x: np.sort(x, -1),
              grad=False),
    "argsort": S(lambda: [f(3, 6)], {"axis": -1},
                 ref=lambda x: np.argsort(x, -1).astype(np.float32),
                 grad=False),
    "topk": S(lambda: [sep(3, 6)], {"k": 2, "ret_typ": "value"}, grad=False,
              ref=lambda x: np.sort(x, -1)[:, :-3:-1]),
    "cumsum": S(lambda: [f(3, 4)], {"axis": 1},
                ref=lambda x: np.cumsum(x, 1)),
    "cumprod": S(lambda: [fpos(3, 4)], {"axis": 1},
                 ref=lambda x: np.cumprod(x, 1)),
    "cummax": S(lambda: [f(3, 4)], {"axis": 1},
                ref=lambda x: np.maximum.accumulate(x, 1), grad=False),
    "cummin": S(lambda: [f(3, 4)], {"axis": 1},
                ref=lambda x: np.minimum.accumulate(x, 1), grad=False),
    # bitwise / int
    "bitwise_and": S(lambda: [ints(3, 4), ints(3, 4)],
                     ref=np.bitwise_and, grad=False),
    "bitwise_or": S(lambda: [ints(3, 4), ints(3, 4)],
                    ref=np.bitwise_or, grad=False),
    "bitwise_xor": S(lambda: [ints(3, 4), ints(3, 4)],
                     ref=np.bitwise_xor, grad=False),
    "bitwise_not": S(lambda: [ints(3, 4)], ref=np.bitwise_not, grad=False),
    "bitwise_left_shift": S(lambda: [ints(3, 4), ints(3, 4, hi=3)],
                            ref=np.left_shift, grad=False),
    "bitwise_right_shift": S(lambda: [ints(3, 4, lo=4, hi=64),
                                      ints(3, 4, hi=3)],
                             ref=np.right_shift, grad=False),
    # special binary
    "prelu": S(lambda: [f(3, 4), fpos(1)],
               ref=lambda x, g: np.where(x >= 0, x, g * x)),
    "polygamma": S(lambda: [fpos(3)], {"n": 1}, grad=False,
                   ref=lambda x: _sp.polygamma(1, x).astype(np.float32)),
    "gammainc": S(lambda: [fpos(3), fpos(3)], grad=False,
                  ref=lambda a, x: _sp.gammainc(a, x)),
    "gammaincc": S(lambda: [fpos(3), fpos(3)], grad=False,
                   ref=lambda a, x: _sp.gammaincc(a, x)),
    # windows / creation
    "hanning": S(lambda: [], {"M": 8}, ref=lambda: np.hanning(8),
                 grad=False, rtol=1e-5, atol=1e-6),
    "hamming": S(lambda: [], {"M": 8}, ref=lambda: np.hamming(8),
                 grad=False, rtol=1e-5, atol=1e-6),
    "blackman": S(lambda: [], {"M": 8}, ref=lambda: np.blackman(8),
                  grad=False, rtol=1e-5, atol=1e-5),
    # sequence ops
    "sequence_mask": S(
        lambda: [f(4, 2, 3), np.array([2, 4], np.int32)],
        {"use_sequence_length": True}, grad=False,
        ref=lambda x, lens: _seq_mask_ref(x, lens)),
    "SequenceLast": S(
        lambda: [f(4, 2, 3), np.array([2, 4], np.int32)],
        {"use_sequence_length": True}, grad=False,
        ref=lambda x, lens: x[lens.astype(int) - 1,
                              np.arange(x.shape[1])]),
    "SequenceReverse": S(
        lambda: [f(4, 2, 3), np.array([2, 4], np.int32)],
        {"use_sequence_length": True}, grad=False,
        ref=lambda x, lens: np.stack(
            [np.concatenate([x[:L, b][::-1], x[L:, b]])
             for b, L in enumerate(lens.astype(int))], 1)),
    # NN layers (layer semantics tested in test_gluon; battery = sanity+grad)
    "FullyConnected": S(lambda: [f(3, 4), f(5, 4), f(5)],
                        {"num_hidden": 5},
                        ref=lambda x, w, b: x @ w.T + b),
    "Convolution": S(lambda: [f(1, 2, 5, 5), f(3, 2, 3, 3), f(3)],
                     {"kernel": (3, 3), "num_filter": 3}, grad=False,
                     ref=lambda x, w, b: _conv2d_ref(x, w, b)),
    "Deconvolution": S(lambda: [f(1, 2, 4, 4), f(2, 3, 3, 3), f(3)],
                       {"kernel": (3, 3), "num_filter": 3}, grad=False),
    "Pooling": S(lambda: [f(1, 2, 4, 4)],
                 {"kernel": (2, 2), "pool_type": "max", "stride": (2, 2)},
                 grad=False, ref=lambda x: _pool_max_ref(x, 2, 2)),
    "Activation": S(lambda: [f(3, 4)], {"act_type": "relu"},
                    ref=lambda x: np.maximum(x, 0)),
    "LeakyReLU": S(lambda: [f(3, 4)], {"act_type": "leaky", "slope": 0.1},
                   ref=lambda x: np.where(x > 0, x, 0.1 * x)),
    "BatchNorm": S(lambda: [f(2, 3, 4, 4), np.ones(3, np.float32),
                            np.zeros(3, np.float32),
                            np.zeros(3, np.float32),
                            np.ones(3, np.float32)], grad=False,
                   ref=lambda x, g, b, mm, mv:
                   (x - x.mean((0, 2, 3), keepdims=True))
                   / np.sqrt(x.var((0, 2, 3), keepdims=True) + 1e-5)),
    "LayerNorm": S(lambda: [f(3, 4), np.ones(4, np.float32),
                            np.zeros(4, np.float32)], grad=False,
                   rtol=1e-3, atol=1e-3,
                   ref=lambda x, g, b: (x - x.mean(-1, keepdims=True))
                   / np.sqrt(x.var(-1, keepdims=True) + 1e-5)),
    "GroupNorm": S(lambda: [f(2, 4, 3), np.ones(4, np.float32),
                            np.zeros(4, np.float32)], {"num_groups": 2},
                   grad=False, rtol=1e-3, atol=1e-3,
                   ref=lambda x, g, b:
                   ((x.reshape(2, 2, 2, 3)
                     - x.reshape(2, 2, 2, 3).mean((2, 3), keepdims=True))
                    / np.sqrt(x.reshape(2, 2, 2, 3).var((2, 3),
                                                        keepdims=True)
                              + 1e-5)).reshape(2, 4, 3)),
    "InstanceNorm": S(lambda: [f(2, 3, 4), np.ones(3, np.float32),
                               np.zeros(3, np.float32)], grad=False,
                      rtol=1e-3, atol=1e-3,
                      ref=lambda x, g, b: (x - x.mean(-1, keepdims=True))
                      / np.sqrt(x.var(-1, keepdims=True) + 1e-3)),
    "RMSNorm": S(lambda: [f(3, 4), np.ones(4, np.float32)], grad=False,
                 rtol=1e-3, atol=1e-3,
                 ref=lambda x, g: x / np.sqrt(
                     (x * x).mean(-1, keepdims=True) + 1e-6)),
    "rotary_embedding": S(
        lambda: [f(2, 3, 8)], params={"num_heads": 2, "rotary_dim": 2,
                                      "theta": 100.0},
        ref=lambda x: np.concatenate(
            [x.reshape(2, 3, 2, 4)[..., :2],
             x.reshape(2, 3, 2, 4)[..., 2:3] * np.cos(np.arange(3))[
                 None, :, None, None]
             - x.reshape(2, 3, 2, 4)[..., 3:] * np.sin(np.arange(3))[
                 None, :, None, None],
             x.reshape(2, 3, 2, 4)[..., 3:] * np.cos(np.arange(3))[
                 None, :, None, None]
             + x.reshape(2, 3, 2, 4)[..., 2:3] * np.sin(np.arange(3))[
                 None, :, None, None]], -1).reshape(2, 3, 8)),
    "causal_conv1d": S(lambda: _own_draw((2, 6, 3), (3, 4), (3,)),
                       ref=lambda x, w, b: _silu(sum(
                           np.pad(x, ((0, 0), (3, 0), (0, 0)))[:, k:k + 6]
                           * w[:, k] for k in range(4)) + b)),
    "ssm_scan": S(lambda: _own_draw((1, 8, 4), (1, 8, 3), (1, 8, 3), (1, 8, 2),
                                    (1, 8, 4), (2,), (2,), (2,)),
                  params={"num_heads": 2, "num_groups": 1, "chunk": 4},
                  ref=lambda *a: _ssm_scan_ref(*a)),
    "L2Normalization": S(lambda: [f(3, 4)],
                         ref=lambda x: x / np.sqrt(
                             (x * x).sum(1, keepdims=True) + 1e-10)),
    "Embedding": S(lambda: [ints(5, hi=7), f(7, 4)],
                   {"input_dim": 7, "output_dim": 4},
                   ref=lambda i, w: w[i], grad=False),
    "Dropout": S(lambda: [f(3, 4)], {"p": 0.0}, ref=lambda x: x,
                 grad=False),
    "SoftmaxOutput": S(lambda: [f(3, 4), ints(3, hi=4)], grad=False,
                       ref=lambda x, y: np.exp(x - x.max(-1, keepdims=True))
                       / np.exp(x - x.max(-1, keepdims=True)).sum(
                           -1, keepdims=True)),
    "UpSampling": S(lambda: [f(1, 2, 3, 3)],
                    {"scale": 2, "sample_type": "nearest"}, grad=False,
                    ref=lambda x: x.repeat(2, 2).repeat(2, 3)),
    "AdaptiveAvgPooling2D": S(lambda: [f(1, 2, 4, 4)],
                              {"output_size": (2, 2)}, grad=False,
                              ref=lambda x: x.reshape(1, 2, 2, 2, 2, 2)
                              .mean((3, 5))),
    "BilinearResize2D": S(lambda: [f(1, 2, 4, 4)],
                          {"height": 8, "width": 8}, grad=False),
    "Cast": S(lambda: [f(3, 4)], {"dtype": "float32"}, ref=lambda x: x),
    "im2col": S(lambda: [f(1, 2, 4, 4)],
                {"kernel": (3, 3), "stride": (1, 1)}, grad=False),
    # spatial
    "GridGenerator": S(lambda: [np.array([[1, 0, 0, 0, 1, 0]], np.float32)],
                       {"transform_type": "affine", "target_shape": (4, 4)},
                       grad=False),
    "BilinearSampler": S(
        lambda: [f(1, 2, 4, 4),
                 np.stack(np.meshgrid(np.linspace(-1, 1, 4),
                                      np.linspace(-1, 1, 4)))[None].astype(
                     np.float32)], grad=False),
    "SpatialTransformer": S(
        lambda: [f(1, 2, 4, 4), np.array([[1, 0, 0, 0, 1, 0]], np.float32)],
        {"target_shape": (4, 4)}, grad=False),
    "ROIPooling": S(lambda: [f(1, 2, 6, 6),
                             np.array([[0, 0, 0, 4, 4]], np.float32)],
                    {"pooled_size": (2, 2), "spatial_scale": 1.0},
                    grad=False),
    "_contrib_ROIAlign": S(lambda: [f(1, 2, 6, 6),
                                    np.array([[0, 0, 0, 4, 4]], np.float32)],
                           {"pooled_size": (2, 2), "spatial_scale": 1.0},
                           grad=False),
    "Correlation": S(lambda: [f(1, 2, 4, 4), f(1, 2, 4, 4)],
                     {"max_displacement": 1}, grad=False),
    # random (moment checks happen in test_forward sanity)
    "_random_uniform": S(lambda: [], {"shape": (500,)}, grad=False),
    "_random_normal": S(lambda: [], {"shape": (500,)}, grad=False),
    "_random_gamma": S(lambda: [], {"alpha": 2.0, "beta": 1.0,
                                    "shape": (64,)}, grad=False),
    "_random_exponential": S(lambda: [], {"lam": 1.0, "shape": (64,)},
                             grad=False),
    "_random_f": S(lambda: [], {"dfnum": 5.0, "dfden": 8.0,
                                "shape": (64,)}, grad=False),
    "_random_geometric": S(lambda: [], {"p": 0.4, "shape": (64,)},
                           grad=False),
    "_random_power": S(lambda: [], {"a": 2.0, "shape": (64,)},
                       grad=False),
    "_random_poisson": S(lambda: [], {"lam": 2.0, "shape": (64,)},
                         grad=False),
    "_random_randint": S(lambda: [], {"low": 0, "high": 5, "shape": (64,)},
                         grad=False),
    "_random_bernoulli": S(lambda: [], {"prob": 0.4, "shape": (64,)},
                           grad=False),
    "_sample_multinomial": S(
        lambda: [np.full((3, 4), 0.25, np.float32)], {"shape": 2},
        grad=False),
    "sample_normal_like": S(lambda: [f(8)], grad=False),
    "shuffle": S(lambda: [f(8, 2)], grad=False),
    # detection
    "MultiBoxPrior": S(lambda: [f(1, 2, 3, 3)],
                       {"sizes": (0.5,), "ratios": (1.0,)}, grad=False),
    "MultiBoxTarget": S(
        lambda: [_anchors(), np.array([[[0, .1, .1, .4, .4]]], np.float32),
                 np.zeros((1, 3, 9), np.float32)], grad=False),
    "MultiBoxDetection": S(
        lambda: [np.full((1, 3, 9), 1 / 3, np.float32),
                 np.zeros((1, 36), np.float32), _anchors()], grad=False),
    "_contrib_box_nms": S(
        lambda: [np.array([[[0, .9, 0, 0, 1, 1], [0, .8, 0, 0, 1, 1]]],
                          np.float32)], grad=False),
    "_contrib_box_iou": S(lambda: [_boxes(3), _boxes(2)], grad=False,
                          ref=lambda a, b: _iou_ref(a, b)),
})


# --- scalar-operand family (reference: elemwise_binary_scalar_op*) --------
_SCALAR_REFS = {
    "_plus_scalar": lambda x, s: x + s,
    "_minus_scalar": lambda x, s: x - s,
    "_rminus_scalar": lambda x, s: s - x,
    "_mul_scalar": lambda x, s: x * s,
    "_div_scalar": lambda x, s: x / s,
    "_rdiv_scalar": lambda x, s: s / x,
    "_mod_scalar": lambda x, s: np.mod(x, s),
    "_power_scalar": lambda x, s: np.power(np.abs(x) + 0.5, s),
    "_rpower_scalar": lambda x, s: np.power(s, x),
    "_maximum_scalar": lambda x, s: np.maximum(x, s),
    "_minimum_scalar": lambda x, s: np.minimum(x, s),
    "_hypot_scalar": lambda x, s: np.hypot(x, s),
    "_equal_scalar": lambda x, s: (x == s).astype(np.float32),
    "_not_equal_scalar": lambda x, s: (x != s).astype(np.float32),
    "_greater_scalar": lambda x, s: (x > s).astype(np.float32),
    "_greater_equal_scalar": lambda x, s: (x >= s).astype(np.float32),
    "_lesser_scalar": lambda x, s: (x < s).astype(np.float32),
    "_lesser_equal_scalar": lambda x, s: (x <= s).astype(np.float32),
    "_logical_and_scalar":
        lambda x, s: np.logical_and(x, s).astype(np.float32),
    "_logical_or_scalar":
        lambda x, s: np.logical_or(x, s).astype(np.float32),
    "_logical_xor_scalar":
        lambda x, s: np.logical_xor(x, s).astype(np.float32),
}
for _name, _sref in _SCALAR_REFS.items():
    SPECS[_name] = S(lambda: [f(3, 4)], {"scalar": 0.7},
                     ref=(lambda r=_sref: lambda x: r(x, 0.7))())
SPECS["_power_scalar"] = S(lambda: [fpos(3, 4)], {"scalar": 1.3},
                           ref=lambda x: np.power(x, 1.3))
# numeric gradient is undefined at the min/max kink: keep the scalar
# OUTSIDE the f() value range (±[0.3, 0.9])
SPECS["_maximum_scalar"] = S(lambda: [f(3, 4)], {"scalar": 1.5},
                             ref=lambda x: np.maximum(x, 1.5))
SPECS["_minimum_scalar"] = S(lambda: [f(3, 4)], {"scalar": 1.5},
                             ref=lambda x: np.minimum(x, 1.5))
SPECS["_rmod_scalar"] = S(lambda: [fpos(3, 4)], {"scalar": 0.7},
                          ref=lambda x: np.mod(0.7, x))
SPECS["smooth_l1_scalar"] = S(
    lambda: [f(3, 4)], {"scalar": 1.0},
    ref=lambda x: np.where(np.abs(x) < 1, 0.5 * x * x, np.abs(x) - 0.5))

SPECS.update({
    # creation (init_op.cc)
    "_zeros": S(lambda: [], {"shape": (3, 4)},
                ref=lambda: np.zeros((3, 4), np.float32)),
    "_ones": S(lambda: [], {"shape": (3, 4)},
               ref=lambda: np.ones((3, 4), np.float32)),
    "_full": S(lambda: [], {"shape": (2, 3), "value": 2.5},
               ref=lambda: np.full((2, 3), 2.5, np.float32)),
    "_arange": S(lambda: [], {"start": 1.0, "stop": 7.0, "step": 2.0},
                 ref=lambda: np.arange(1.0, 7.0, 2.0, np.float32)),
    "_linspace": S(lambda: [], {"start": 0.0, "stop": 1.0, "num": 5},
                   ref=lambda: np.linspace(0, 1, 5, dtype=np.float32)),
    "_eye": S(lambda: [], {"N": 3, "M": 4, "k": 1},
              ref=lambda: np.eye(3, 4, 1, dtype=np.float32)),
    # misc tail
    "add_n": S(lambda: [f(3, 4), f(3, 4), f(3, 4)],
               ref=lambda a, b, c: a + b + c),
    "all_finite": S(lambda: [f(3, 4)],
                    ref=lambda x: np.float32([np.isfinite(x).all()])),
    "multi_all_finite": S(lambda: [f(3), f(3)], {"num_arrays": 2},
                          ref=lambda a, b: np.float32([1.0])),
    "amp_multicast": S(lambda: [f(3, 4), f(3, 4)], {"num_outputs": 2},
                       ref=lambda a, b: (a, b)),
    "cast_storage": S(lambda: [f(3, 4)], {"stype": "default"},
                      ref=lambda x: x),
    "_copyto": S(lambda: [f(3, 4)], ref=lambda x: x),
    "choose_element_0index": S(
        lambda: [f(4, 5), ints(4, hi=5).astype(np.float32)], grad=False,
        ref=lambda x, i: x[np.arange(4), i.astype(np.int64)]),
    "fill_element_0index": S(
        lambda: [f(4, 5), f(4), ints(4, hi=5).astype(np.float32)],
        grad=False,
        ref=lambda x, v, i: _fill_ref(x, v, i)),
    "reshape_like": S(lambda: [f(2, 6), f(3, 4)], ref=lambda a, b: a.reshape(3, 4)),
    "broadcast_like": S(lambda: [f(1, 4), f(3, 4)],
                        ref=lambda a, b: np.broadcast_to(a, (3, 4))),
    "diff": S(lambda: [f(3, 6)], {"n": 1, "axis": -1},
              ref=lambda x: np.diff(x, axis=-1)),
    "_onehot_encode": S(lambda: [ints(4, hi=5).astype(np.float32), f(4, 5)],
                        grad=False,
                        ref=lambda i, o: np.eye(5, dtype=np.float32)[
                            i.astype(np.int64)]),
    "_sparse_retain": S(
        lambda: [f(5, 3), np.array([0, 2], np.int32)], grad=False,
        ref=lambda x, i: np.where(
            np.isin(np.arange(5), i)[:, None], x, 0).astype(np.float32)),
    "softmax_with_length": S(
        lambda: [f(2, 5), np.array([3, 5], np.int32)], grad=False,
        ref=lambda x, ln: np.stack([
            np.concatenate([
                np.exp(x[b, :ln[b]]) / np.exp(x[b, :ln[b]]).sum(),
                np.zeros(5 - ln[b], np.float32)])
            for b in range(2)])),
    "_scatter_set_nd": S(
        lambda: [f(4, 5), f(2), np.array([[0, 2], [1, 3]], np.int32)],
        grad=False,
        ref=lambda l, r, i: _index_set_ref(l, (i[0], i[1]), r)),
    "IdentityAttachKLSparseReg": S(lambda: [fpos(4, 3)], grad=False,
                                   ref=lambda x: x),
    "_contrib_arange_like": S(lambda: [f(2, 3)], {"axis": 1}, grad=False,
                              ref=lambda x: np.arange(3, dtype=np.float32)),
    "_contrib_div_sqrt_dim": S(lambda: [f(3, 4)],
                               ref=lambda x: x / np.sqrt(4)),
    "_contrib_gradientmultiplier": S(lambda: [f(3, 4)], {"scalar": 1.0},
                                     ref=lambda x: x),
    "_contrib_index_array": S(lambda: [f(2, 3)], grad=False,
                              ref=lambda x: np.stack(
                                  np.indices(x.shape), -1)),
    "_contrib_allclose": S(lambda: [f(3, 4), f(3, 4)], grad=False,
                           ref=lambda a, b: np.asarray(
                               np.allclose(a, b), np.float32)),
    "_contrib_quadratic": S(lambda: [f(3, 4)],
                            {"a": 1.0, "b": 2.0, "c": 3.0},
                            ref=lambda x: x * x + 2 * x + 3),
    "_contrib_fft": S(
        lambda: [f(2, 8)], grad=False,
        ref=lambda x: np.stack([np.fft.fft(x, axis=-1).real,
                                np.fft.fft(x, axis=-1).imag],
                               axis=-1).reshape(2, 16).astype(np.float32)),
    "_contrib_ifft": S(
        lambda: [f(2, 16)], grad=False,
        ref=lambda x: np.fft.ifft(
            x.reshape(2, 8, 2)[..., 0] + 1j * x.reshape(2, 8, 2)[..., 1],
            axis=-1).real.astype(np.float32)),
    "_contrib_bipartite_matching": S(
        lambda: [np.array([[0.9, 0.1], [0.8, 0.7]], np.float32)],
        grad=False,
        ref=lambda x: (np.array([0., 1.], np.float32),
                       np.array([0., 1.], np.float32))),
    "_contrib_getnnz": S(lambda: [f(3, 4)], grad=False,
                         ref=lambda x: np.asarray(
                             (x != 0).sum(), np.int64)),
    "_contrib_dynamic_reshape": S(
        lambda: [f(2, 6), np.array([3, 4], np.int32)], grad=False,
        ref=lambda x, s: x.reshape(3, 4)),
    "_contrib_count_sketch": S(
        lambda: [f(3, 6), ints(6, hi=4).astype(np.float32),
                 R.choice([-1.0, 1.0], 6).astype(np.float32)],
        {"out_dim": 4}, grad=False, ref=None),
    "_contrib_hawkesll": S(
        lambda: [fpos(2, 3), fpos(3), fpos(3), fpos(2, 3),
                 fpos(2, 4), ints(2, 4, hi=3).astype(np.float32),
                 np.array([4, 3], np.float32),
                 np.array([10.0, 10.0], np.float32)],
        grad=False, ref=None),
    "_rnn_param_concat": S(lambda: [f(6), f(4)], {"dim": 0},
                           ref=lambda a, b: np.concatenate([a, b])),
    "col2im": S(
        lambda: [_im2col_np(f(1, 2, 4, 4))],
        {"output_size": (4, 4), "kernel": (2, 2), "stride": (2, 2)},
        grad=False, ref=None),
    # optimizer tail (update semantics pinned in test_optimizer for the
    # single-weight rows; here forward sanity for the fused fleets)
    # update-rule refs re-derived from the published formulas (FTML paper,
    # NAG, LAMB paper, decoupled AdamW) — independent of the op impls
    "ftml_update": S(lambda: [f(4), f(4), fpos(4), fpos(4), f(4)],
                     {"lr": 0.01, "t": 1}, grad=False,
                     ref=lambda w, g, d, v, z, b1=0.6, b2=0.999, e=1e-8:
                     -(b1 * z + (1 - b1) * g
                       - ((1 - b1) / 0.01 * (np.sqrt(
                           (b2 * v + (1 - b2) * g * g) / (1 - b2)) + e)
                          - b1 * d) * w)
                     / ((1 - b1) / 0.01 * (np.sqrt(
                         (b2 * v + (1 - b2) * g * g) / (1 - b2)) + e))),
    "mp_nag_mom_update": S(
        lambda: [f(4), f(4), f(4), f(4)], {"lr": 0.01, "momentum": 0.9},
        grad=False,
        ref=lambda w, g, m, w32: w32 - 0.01 * (g + 0.9 * (0.9 * m + g))),
    "mp_lamb_update_phase1": S(
        lambda: [f(4), f(4), f(4), fpos(4)], {"t": 1}, grad=False,
        ref=lambda g, w32, m, v, b1=0.9, b2=0.999, e=1e-6:
        ((b1 * m + (1 - b1) * g) / (1 - b1))
        / (np.sqrt((b2 * v + (1 - b2) * g * g) / (1 - b2)) + e)),
    "mp_lamb_update_phase2": S(
        lambda: [f(4), f(4), np.array(1.0, np.float32),
                 np.array(1.0, np.float32), f(4)],
        {"lr": 0.01}, grad=False,
        ref=lambda w, gu, r1, r2, w32: w32 - 0.01 * (r1 / r2) * gu),
    "mp_adamw_update": S(
        lambda: [f(4), f(4), f(4), fpos(4), f(4),
                 np.array(1.0, np.float32)],
        {"lr": 0.01}, grad=False,
        ref=lambda w, g, m, v, w32, rs, b1=0.9, b2=0.999, e=1e-8:
        w32 - 0.01 * (b1 * m + (1 - b1) * g)
        / (np.sqrt(b2 * v + (1 - b2) * g * g) + e)),
    "_contrib_group_adagrad_update": S(
        lambda: [f(4, 3), f(4, 3), fpos(4, 1)], {"lr": 0.01}, grad=False,
        ref=lambda w, g, h: w - 0.01 * g / (np.sqrt(
            h + (g * g).mean(1, keepdims=True)) + 1e-5)),
    "multi_sgd_update": S(
        lambda: [f(4), f(4), f(3), f(3)],
        {"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2},
        grad=False, ref=lambda w0, g0, w1, g1: (w0 - 0.1 * g0,
                                                w1 - 0.1 * g1)),
    "multi_sgd_mom_update": S(
        lambda: [f(4), f(4), np.zeros(4, np.float32),
                 f(3), f(3), np.zeros(3, np.float32)],
        {"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2},
        # all outputs are written back in place -> invisible to
        # test_forward; pinned by test_fleet_update_writeback
        grad=False),
    "multi_mp_sgd_update": S(
        lambda: [f(4), f(4), f(4), f(3), f(3), f(3)],
        {"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2},
        grad=False),
    "multi_mp_sgd_mom_update": S(
        lambda: [f(4), f(4), np.zeros(4, np.float32), f(4),
                 f(3), f(3), np.zeros(3, np.float32), f(3)],
        {"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2},
        grad=False),
    "multi_sum_sq": S(lambda: [f(4), f(3)], {"num_arrays": 2}, grad=False,
                      ref=lambda a, b: np.array([np.sum(a * a),
                                                 np.sum(b * b)],
                                                np.float32)),
    "multi_lars": S(
        lambda: [fpos(3), fpos(3), fpos(3), np.zeros(3, np.float32)],
        {"eta": 0.001}, grad=False,
        ref=lambda lrs, wsq, gsq, wds: lrs * np.where(
            (np.sqrt(wsq) > 0) & (np.sqrt(gsq) > 0),
            0.001 * np.sqrt(wsq) / (np.sqrt(gsq) + wds * np.sqrt(wsq)
                                    + 1e-8), 1.0)),
    "preloaded_multi_sgd_update": S(
        lambda: [f(4), f(4), f(3), f(3),
                 np.array([0.1, 0.1], np.float32),
                 np.zeros(2, np.float32)],
        {"num_weights": 2}, grad=False,
        ref=lambda w0, g0, w1, g1, lrs, wds: (w0 - 0.1 * g0,
                                              w1 - 0.1 * g1)),
    "preloaded_multi_sgd_mom_update": S(
        lambda: [f(4), f(4), np.zeros(4, np.float32),
                 f(3), f(3), np.zeros(3, np.float32),
                 np.array([0.1, 0.1], np.float32),
                 np.zeros(2, np.float32)],
        {"num_weights": 2}, grad=False),
    "preloaded_multi_mp_sgd_update": S(
        lambda: [f(4), f(4), f(4), f(3), f(3), f(3),
                 np.array([0.1, 0.1], np.float32),
                 np.zeros(2, np.float32)],
        {"num_weights": 2}, grad=False),
    "preloaded_multi_mp_sgd_mom_update": S(
        lambda: [f(4), f(4), np.zeros(4, np.float32), f(4),
                 f(3), f(3), np.zeros(3, np.float32), f(3),
                 np.array([0.1, 0.1], np.float32),
                 np.zeros(2, np.float32)],
        {"num_weights": 2}, grad=False),
    "reset_arrays": S(lambda: [f(3), f(4)], {"num_arrays": 2}, grad=False,
                      ref=lambda a, b: (np.zeros_like(a),
                                        np.zeros_like(b))),
    # nn tail
    "LRN": S(lambda: [f(2, 6, 4, 4)], {"nsize": 3}, grad=False,
             ref=_lrn_ref),
    "BlockGrad": S(lambda: [f(3, 4)], grad=False, ref=lambda x: x),
    "MakeLoss": S(lambda: [fpos(3, 4)], grad=False, ref=lambda x: x),
    "SVMOutput": S(lambda: [f(4, 5), ints(4, hi=5).astype(np.float32)],
                   grad=False, ref=lambda x, y: x),
    "SoftmaxActivation": S(
        lambda: [f(3, 4)], grad=False,
        ref=lambda x: np.exp(x - x.max(-1, keepdims=True))
        / np.exp(x - x.max(-1, keepdims=True)).sum(-1, keepdims=True)),
    "Crop": S(lambda: [f(1, 2, 6, 6)],
              {"offset": (1, 1), "h_w": (4, 4), "num_args": 1},
              grad=False, ref=lambda x: x[:, :, 1:5, 1:5]),
    "_contrib_BatchNormWithReLU": S(
        lambda: [f(2, 3, 4, 4), np.ones(3, np.float32),
                 np.zeros(3, np.float32), np.zeros(3, np.float32),
                 np.ones(3, np.float32)], grad=False, ref=None),
    "_contrib_SyncBatchNorm": S(
        lambda: [f(2, 3, 4, 4), np.ones(3, np.float32),
                 np.zeros(3, np.float32), np.zeros(3, np.float32),
                 np.ones(3, np.float32)], grad=False, ref=None),
    # image ops
    "_image_to_tensor": S(
        lambda: [ints(4, 5, 3, hi=255).astype(np.uint8)], grad=False,
        ref=lambda x: (x.astype(np.float32) / 255).transpose(2, 0, 1)),
    "_image_normalize": S(
        lambda: [fpos(3, 4, 5)],
        {"mean": (0.5, 0.5, 0.5), "std": (0.2, 0.2, 0.2)}, grad=False,
        ref=lambda x: (x - 0.5) / 0.2),
    "_image_resize": S(lambda: [ints(6, 8, 3, hi=255).astype(np.uint8)],
                       {"size": (4, 3)}, grad=False, ref=None),
    "_image_crop": S(lambda: [ints(6, 8, 3, hi=255).astype(np.uint8)],
                     {"x": 1, "y": 2, "width": 4, "height": 3}, grad=False,
                     ref=lambda x: x[2:5, 1:5, :]),
    "_image_flip_left_right": S(
        lambda: [fpos(4, 5, 3)], grad=False, ref=lambda x: x[:, ::-1, :]),
    "_image_flip_top_bottom": S(
        lambda: [fpos(4, 5, 3)], grad=False, ref=lambda x: x[::-1, :, :]),
    "_image_adjust_lighting": S(
        lambda: [fpos(4, 5, 3)], {"alpha": (0.0, 0.0, 0.0)}, grad=False,
        ref=lambda x: x),
    "_image_random_brightness": S(
        lambda: [fpos(4, 5, 3)], {"min_factor": 0.5, "max_factor": 1.5},
        grad=False),
    "_image_random_contrast": S(
        lambda: [fpos(4, 5, 3)], {"min_factor": 0.5, "max_factor": 1.5},
        grad=False),
    "_image_random_saturation": S(
        lambda: [fpos(4, 5, 3)], {"min_factor": 0.5, "max_factor": 1.5},
        grad=False),
    "_image_random_hue": S(
        lambda: [fpos(4, 5, 3)], {"min_factor": -0.1, "max_factor": 0.1},
        grad=False),
    "_image_random_color_jitter": S(
        lambda: [fpos(4, 5, 3)],
        {"brightness": 0.2, "contrast": 0.2, "saturation": 0.2,
         "hue": 0.05}, grad=False),
    "_image_random_lighting": S(lambda: [fpos(4, 5, 3)],
                                {"alpha_std": 0.05}, grad=False),
    "_image_random_flip_left_right": S(lambda: [fpos(4, 5, 3)], grad=False),
    "_image_random_flip_top_bottom": S(lambda: [fpos(4, 5, 3)], grad=False),
    "_image_imdecode": S(lambda: [_jpeg_bytes()], grad=False, ref=None),
    # random tail
    "_random_negative_binomial": S(
        lambda: [], {"k": 3, "p": 0.5, "shape": (64,)}, grad=False),
    "_random_generalized_negative_binomial": S(
        lambda: [], {"mu": 2.0, "alpha": 0.3, "shape": (64,)}, grad=False),
    "_random_pareto": S(lambda: [], {"a": 2.0, "shape": (64,)}, grad=False),
    "_random_rayleigh": S(lambda: [], {"scale": 1.5, "shape": (64,)},
                          grad=False),
    "_random_weibull": S(lambda: [], {"a": 1.5, "shape": (64,)}, grad=False),
    "_random_logistic": S(lambda: [], {"loc": 0.0, "scale": 1.0,
                                       "shape": (64,)}, grad=False),
    "_random_gumbel": S(lambda: [], {"loc": 0.0, "scale": 1.0,
                                     "shape": (64,)}, grad=False),
    "_sample_uniform": S(lambda: [np.zeros(3, np.float32),
                                  np.ones(3, np.float32)],
                         {"shape": (5,)}, grad=False),
    "_sample_normal": S(lambda: [f(3), fpos(3)], {"shape": (5,)},
                        grad=False),
    "_sample_gamma": S(lambda: [fpos(3) + 1, fpos(3)], {"shape": (5,)},
                       grad=False),
    "_sample_exponential": S(lambda: [fpos(3)], {"shape": (5,)},
                             grad=False),
    "_sample_poisson": S(lambda: [fpos(3) * 3], {"shape": (5,)},
                         grad=False),
    "_sample_negative_binomial": S(
        lambda: [np.array([2., 3., 4.], np.float32), fpos(3)],
        {"shape": (5,)}, grad=False),
    "_sample_generalized_negative_binomial": S(
        lambda: [fpos(3) * 2, fpos(3)], {"shape": (5,)}, grad=False),
    "_sample_unique_zipfian": S(lambda: [], {"range_max": 100,
                                             "shape": (8,)}, grad=False),
    # detection tail
    "_contrib_box_encode": S(
        lambda: [np.ones((1, 2), np.float32),
                 np.zeros((1, 2), np.float32),
                 np.array([[[0., 0., 1., 1.], [1., 1., 2., 2.]]],
                          np.float32),
                 np.array([[[0., 0., 1., 1.]]], np.float32)],
        grad=False, ref=None),
    "_contrib_box_decode": S(
        lambda: [np.zeros((1, 2, 4), np.float32),
                 np.array([[[0., 0., 1., 1.], [1., 1., 2., 2.]]],
                          np.float32)],
        grad=False,
        ref=lambda d, a: a),
    "_contrib_PSROIPooling": S(
        lambda: [fpos(1, 8, 6, 6),
                 np.array([[0, 0, 0, 4, 4]], np.float32)],
        {"spatial_scale": 1.0, "output_dim": 2, "pooled_size": 2},
        grad=False, ref=None),
    "Proposal": S(
        lambda: [fpos(1, 6, 4, 4), f(1, 12, 4, 4) * 0.1,
                 np.array([64., 64., 1.], np.float32)],
        {"scales": (8,), "ratios": (0.5, 1, 2), "rpn_pre_nms_top_n": 12,
         "rpn_post_nms_top_n": 4, "feature_stride": 16},
        grad=False, ref=None),
    "MultiProposal": S(
        lambda: [fpos(2, 6, 4, 4), f(2, 12, 4, 4) * 0.1,
                 np.array([64., 64., 1.], np.float32)],
        {"scales": (8,), "ratios": (0.5, 1, 2), "rpn_pre_nms_top_n": 12,
         "rpn_post_nms_top_n": 4, "feature_stride": 16},
        grad=False, ref=None),
    "_contrib_DeformableConvolution": S(
        lambda: [fpos(1, 2, 5, 5), np.zeros((1, 18, 5, 5), np.float32),
                 f(3, 2, 3, 3)],
        {"kernel": (3, 3), "pad": (1, 1), "num_filter": 3, "no_bias": True},
        grad=False,
        # zero offsets make deformable conv == plain convolution
        ref=lambda x, off, w: _conv2d_ref(x, w, np.zeros(3, np.float32),
                                          pad=1)),
    # quantized tail (numeric contracts pinned in test_quantization)
    "_contrib_quantized_batch_norm": S(
        lambda: [ints(2, 3, 4, 4, lo=-100, hi=100).astype(np.int8),
                 np.ones(3, np.float32), np.zeros(3, np.float32),
                 np.zeros(3, np.float32), np.ones(3, np.float32),
                 np.array([-1.0], np.float32), np.array([1.0], np.float32)],
        grad=False, ref=None),
    "_contrib_quantized_elemwise_add": S(
        lambda: [ints(3, 4, lo=-100, hi=100).astype(np.int8),
                 ints(3, 4, lo=-100, hi=100).astype(np.int8),
                 np.array([-1.], np.float32), np.array([1.], np.float32),
                 np.array([-1.], np.float32), np.array([1.], np.float32)],
        grad=False, ref=None),
    "_contrib_quantized_elemwise_mul": S(
        lambda: [ints(3, 4, lo=-100, hi=100).astype(np.int8),
                 ints(3, 4, lo=-100, hi=100).astype(np.int8),
                 np.array([-1.], np.float32), np.array([1.], np.float32),
                 np.array([-1.], np.float32), np.array([1.], np.float32)],
        grad=False, ref=None),
    "_contrib_quantized_embedding": S(
        lambda: [ints(5, hi=4).astype(np.float32),
                 ints(4, 6, lo=-100, hi=100).astype(np.int8),
                 np.array([-1.], np.float32), np.array([1.], np.float32)],
        grad=False, ref=None),
    "_contrib_quantized_concat": S(
        lambda: [ints(2, 3, lo=-100, hi=100).astype(np.int8),
                 ints(2, 3, lo=-100, hi=100).astype(np.int8),
                 np.array([-1.], np.float32), np.array([1.], np.float32),
                 np.array([-2.], np.float32), np.array([2.], np.float32)],
        {"num_args": 2, "dim": 0}, grad=False, ref=None),
    "_contrib_calibrate_entropy": S(
        lambda: [np.histogram(np.abs(R.randn(5000)), bins=64,
                              range=(0, 4))[0].astype(np.float32),
                 np.histogram(np.abs(R.randn(5000)), bins=64,
                              range=(0, 4))[1].astype(np.float32)],
        {"num_quantized_bins": 15}, grad=False, ref=None),
    "_contrib_intgemm_maxabsolute": S(
        lambda: [f(3, 4)], grad=False,
        ref=lambda x: np.array([np.abs(x).max()], np.float32)),
    "_contrib_intgemm_prepare_data": S(
        lambda: [f(3, 4), np.array([1.0], np.float32)], grad=False,
        ref=None),
    "_contrib_intgemm_prepare_weight": S(
        lambda: [f(3, 4), np.array([1.0], np.float32)], grad=False,
        ref=None),
    "_contrib_intgemm_take_weight": S(
        lambda: [ints(4, 6, lo=-100, hi=100).astype(np.int8),
                 ints(2, hi=4).astype(np.float32)], grad=False, ref=None),
    "_contrib_intgemm_fully_connected": S(
        lambda: [ints(2, 8, lo=-30, hi=30).astype(np.int8),
                 ints(4, 8, lo=-30, hi=30).astype(np.int8),
                 np.array([0.01], np.float32)],
        {"num_hidden": 4, "no_bias": True}, grad=False,
        ref=lambda x, w, s: (x.astype(np.int32)
                             @ w.astype(np.int32).T).astype(np.float32)
        * 0.01),
})




_MPLANS_W = f(4)


def _lans_ref(w, g, m, v, lr, wd, beta1=0.9, beta2=0.999, eps=1e-6, t=1):
    """NumPy LANS single step (the paper's Algorithm: normalized grad,
    trust ratio on momentum AND gradient terms, each incl. weight decay)."""
    g = g / max(np.sqrt(np.sum(g * g)), 1e-12)
    m1 = beta1 * m + (1 - beta1) * g
    v1 = beta2 * v + (1 - beta2) * g * g
    mh = m1 / (1 - beta1 ** t)
    vh = v1 / (1 - beta2 ** t)
    wn = np.sqrt(np.sum(w * w))

    def trust(u):
        un = np.sqrt(np.sum(u * u))
        return (wn / un if wn > 0 and un > 0 else 1.0) * u
    d = np.sqrt(vh) + eps
    upd = beta1 * trust(mh / d + wd * w) + \
        (1 - beta1) * trust(g / d + wd * w)
    return (w - lr * upd, m1, v1)


_JPEG_FILE = None


def _jpeg_file():
    """One temp jpeg per process, removed at exit (the spec table needs a
    concrete path at build time)."""
    global _JPEG_FILE
    if _JPEG_FILE is None:
        import atexit
        import os as _os
        import tempfile
        from PIL import Image
        fd, path = tempfile.mkstemp(suffix=".jpg")
        _os.close(fd)
        Image.fromarray(ints(8, 8, 3, hi=255).astype(np.uint8)).save(path)
        atexit.register(lambda: _os.path.exists(path) and _os.unlink(path))
        _JPEG_FILE = path
    return _JPEG_FILE


SPECS.update({
    # sliding-window attention (GluonNLP longformer ops)
    "_contrib_sldwin_atten_score": S(
        lambda: [f(1, 8, 2, 4), f(1, 8, 2, 4),
                 np.ones(2, np.float32)], {"w": 2, "symmetric": True},
        grad=False, ref=None),
    "_contrib_sldwin_atten_mask_like": S(
        lambda: [f(1, 8, 2, 5), np.ones(2, np.float32),
                 np.array([8.0], np.float32)], {"w": 2, "symmetric": True},
        grad=False, ref=None),
    "_contrib_sldwin_atten_context": S(
        lambda: [f(1, 8, 2, 5), f(1, 8, 2, 4),
                 np.ones(2, np.float32)], {"w": 2, "symmetric": True},
        grad=False, ref=None),
    # straight-through estimators
    # numeric-vs-autodiff comparison is wrong BY DESIGN for STEs (the
    # straight-through gradient is identity while the true one is 0 a.e.)
    # -> forward ref here, gradient pinned in test_ste_identity_gradient
    "_contrib_round_ste": S(lambda: [f(3, 4)], ref=np.rint, grad=False),
    "_contrib_sign_ste": S(lambda: [f(3, 4)], ref=np.sign, grad=False),
    # opencv-plugin parity
    "_cvimdecode": S(lambda: [_jpeg_bytes()], grad=False, ref=None),
    "_cvimread": S(lambda: [], {"filename": _jpeg_file()}, grad=False,
                   ref=None),
    "_cvimresize": S(lambda: [ints(6, 8, 3, hi=255).astype(np.uint8)],
                     {"w": 4, "h": 3}, grad=False, ref=None),
    "_cvcopyMakeBorder": S(
        lambda: [fpos(3, 4, 3)], {"top": 1, "bot": 1, "left": 2,
                                  "right": 2},
        grad=False,
        ref=lambda x: np.pad(x, ((1, 1), (2, 2), (0, 0))).astype(
            np.float32)),
    # fused adamw fleets
    "multi_lans_update": S(
        lambda: [f(4), f(4), np.zeros(4, np.float32),
                 np.zeros(4, np.float32)],
        {"learning_rates": (0.1,), "wds": (0.01,), "t": 1,
         "num_weights": 1}, grad=False,
        ref=lambda w, g, m, v: _lans_ref(w, g, m, v, 0.1, 0.01)),
    "multi_mp_lans_update": S(
        lambda: [_MPLANS_W.copy(), f(4), np.zeros(4, np.float32),
                 np.zeros(4, np.float32), _MPLANS_W.astype(np.float32)],
        {"learning_rates": (0.1,), "wds": (0.01,), "t": 1,
         "num_weights": 1}, grad=False,
        ref=lambda w, g, m, v, w32: _lans_ref(w32, g, m, v, 0.1, 0.01)),
    "multi_adamw_update": S(
        lambda: [f(4), f(4), f(4), fpos(4), f(3), f(3), f(3), fpos(3),
                 np.array(1.0, np.float32)],
        {"lrs": (0.01, 0.01), "wds": (0.0, 0.0), "num_weights": 2},
        grad=False),
    "multi_mp_adamw_update": S(
        lambda: [f(4), f(4), f(4), fpos(4), f(4),
                 f(3), f(3), f(3), fpos(3), f(3),
                 np.array(1.0, np.float32)],
        {"lrs": (0.01, 0.01), "wds": (0.0, 0.0), "num_weights": 2},
        grad=False),
    # detection tail 2
    "_contrib_edge_id": S(
        lambda: [np.array([0, 2, 3], np.float32),
                 np.array([1, 2, 0], np.float32),
                 np.array([0, 0, 1, 1], np.float32),
                 np.array([2, 0, 0, 2], np.float32)],
        grad=False,
        ref=lambda ip, ix, u, v: np.array([1.0, -1.0, 2.0, -1.0],
                                          np.float32)),
    "_contrib_DeformablePSROIPooling": S(
        lambda: [fpos(1, 8, 6, 6), np.array([[0, 0, 0, 4, 4]], np.float32),
                 np.full((1, 2, 2, 2), 0.5, np.float32)],  # (R, 2, p, p)
        {"spatial_scale": 1.0, "output_dim": 2, "group_size": 2,
         "pooled_size": 2, "part_size": 2, "trans_std": 0.1},
        grad=False, ref=None),
    "Convolution_v1": S(
        lambda: [fpos(1, 2, 5, 5), f(3, 2, 3, 3)],
        {"kernel": (3, 3), "pad": (1, 1), "num_filter": 3, "no_bias": True},
        grad=False,
        ref=lambda x, w: _conv2d_ref(x, w, np.zeros(3, np.float32),
                                     pad=1)),
    "Pooling_v1": S(
        lambda: [fpos(1, 2, 5, 5)],
        {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
        # v1 pooling uses the CEIL output convention (windows clipped at
        # the edge) — that is the v1/v2 behavioural difference
        grad=False, ref=lambda x: _pool_max_ref(x, 2, 2, ceil=True)),
    "_contrib_mrcnn_mask_target": S(
        lambda: [np.array([[[1., 1., 5., 5.]]], np.float32),
                 fpos(1, 2, 8, 8), np.zeros((1, 1), np.float32),
                 np.ones((1, 1), np.float32)],
        {"num_classes": 2, "mask_size": (4, 4)}, grad=False, ref=None),
    "_contrib_ModulatedDeformableConvolution": S(
        lambda: [fpos(1, 2, 5, 5), np.zeros((1, 18, 5, 5), np.float32),
                 np.ones((1, 9, 5, 5), np.float32), f(3, 2, 3, 3)],
        {"kernel": (3, 3), "pad": (1, 1), "num_filter": 3,
         "no_bias": True}, grad=False, ref=None),
})



def _fill_ref(x, v, i):
    y = x.copy()
    np.put_along_axis(y, i.astype(np.int64)[:, None], v[:, None], axis=-1)
    return y


def _im2col_np(x):
    """2x2/stride-2 im2col in the (C, kh, kw)-flattened layout."""
    B, C, H, W = x.shape
    Ho, Wo = H // 2, W // 2
    out = np.zeros((B, C * 4, Ho * Wo), np.float32)
    for c in range(C):
        for i in range(2):
            for j in range(2):
                for l in range(Ho * Wo):
                    out[:, c * 4 + i * 2 + j, l] = \
                        x[:, c, 2 * (l // Wo) + i, 2 * (l % Wo) + j]
    return out


def _jpeg_bytes():
    import io as _io
    from PIL import Image
    img = Image.fromarray(ints(8, 8, 3, hi=255).astype(np.uint8))
    buf = _io.BytesIO()
    img.save(buf, format="JPEG")
    return np.frombuffer(buf.getvalue(), np.uint8).copy()


def _spd(n):
    a = fpos(n, n)
    return (a @ a.T + n * np.eye(n, dtype=np.float32))


def _anchors():
    from mxnet_tpu.ndarray.ndarray import invoke as _inv
    return _inv("MultiBoxPrior", nd.zeros((1, 2, 3, 3)),
                sizes=(0.5,), ratios=(1.0,)).asnumpy()


# --- _npi_* numpy-semantics layer (ops/numpy_ops.py) -----------------------
# Each op mirrors one numpy function, so the reference IS that function.

_NPI_UNARY_GEN = {
    "log": fpos, "log2": fpos, "log10": fpos, "log1p": fpos, "sqrt": fpos,
    "cbrt": fpos, "arccosh": lambda *s: 1.0 + fpos(*s), "arcsin": funit,
    "arccos": funit, "arctanh": funit, "i0": fpos,
}
_NPI_UNARY = [
    "absolute", "fabs", "negative", "positive", "conjugate", "exp", "exp2",
    "expm1", "log", "log2", "log10", "log1p", "sqrt", "cbrt", "square",
    "reciprocal", "sin", "cos", "tan", "arcsin", "arccos", "arctan", "sinh",
    "cosh", "tanh", "arcsinh", "arccosh", "arctanh", "degrees", "radians",
    "deg2rad", "rad2deg", "sinc", "i0", "sign", "signbit", "floor", "ceil",
    "trunc", "rint", "fix", "isnan", "isinf", "isfinite", "isneginf",
    "isposinf", "logical_not", "real", "imag",
]
for _n in _NPI_UNARY:
    _gen = _NPI_UNARY_GEN.get(_n, f)
    SPECS["_npi_" + _n] = S(lambda g=_gen: [g(3, 4)], ref=getattr(np, _n))
SPECS["_npi_bitwise_not"] = S(lambda: [ints(3, 4)], ref=np.bitwise_not)
SPECS["_npi_invert"] = S(lambda: [ints(3, 4)], ref=np.invert)
SPECS["_npi_around"] = S(lambda: [f(3, 4)], {"decimals": 1},
                         ref=lambda x: np.around(x, 1))
SPECS["_npi_nan_to_num"] = S(lambda: [f(3, 4)], ref=np.nan_to_num)

_NPI_BINARY = [
    "add", "subtract", "multiply", "true_divide", "power", "float_power",
    "arctan2", "hypot", "logaddexp", "logaddexp2", "maximum", "minimum",
    "fmax", "fmin", "copysign", "floor_divide", "remainder", "fmod",
    "nextafter", "ldexp", "heaviside", "equal", "not_equal", "less",
    "less_equal", "greater", "greater_equal", "logical_and", "logical_or",
    "logical_xor",
]
for _n in _NPI_BINARY:
    _r = getattr(np, _n)
    if _n in ("power", "float_power"):
        SPECS["_npi_" + _n] = S(lambda: [fpos(3, 4), f(3, 4)], ref=_r)
    else:
        SPECS["_npi_" + _n] = S(lambda: [f(3, 4), fpos(3, 4)], ref=_r,
                                rtol=1e-4, atol=1e-4)
for _n in ("gcd", "lcm", "bitwise_and", "bitwise_or", "bitwise_xor"):
    SPECS["_npi_" + _n] = S(lambda: [ints(2, 5, lo=1), ints(2, 5, lo=1)],
                            ref=getattr(np, _n))
SPECS["_npi_ldexp"] = S(lambda: [f(3, 4), ints(3, 4, hi=4)], ref=np.ldexp)
SPECS["_npi_left_shift"] = S(lambda: [ints(3, 4), ints(3, 4, hi=4)],
                             ref=np.left_shift)
SPECS["_npi_right_shift"] = S(lambda: [ints(3, 4, lo=8, hi=64),
                                       ints(3, 4, hi=3)], ref=np.right_shift)
SPECS["_npi_divmod"] = S(lambda: [f(3, 4), fpos(3, 4)],
                         ref=lambda a, b: np.divmod(a, b))
SPECS["_npi_modf"] = S(lambda: [f(3, 4)], ref=lambda a: np.modf(a))
SPECS["_npi_frexp"] = S(lambda: [fpos(3, 4)], ref=lambda a: np.frexp(a))
SPECS["_npi_isclose"] = S(lambda: [f(3, 4), f(3, 4)], ref=np.isclose)
SPECS["_npi_allclose"] = S(lambda: [f(3, 4), f(3, 4)],
                           ref=lambda a, b: np.asarray(np.allclose(a, b)))
SPECS["_npi_array_equal"] = S(
    lambda: [f(3, 4), f(3, 4)],
    ref=lambda a, b: np.asarray(np.array_equal(a, b)))
SPECS["_npi_array_equiv"] = S(
    lambda: [f(3, 4), f(3, 4)],
    ref=lambda a, b: np.asarray(np.array_equiv(a, b)))

# reductions
for _n in ("sum", "prod", "mean", "nansum", "nanprod", "nanmean", "std",
           "var", "nanstd", "nanvar"):
    SPECS["_npi_" + _n] = S(lambda: [fpos(2, 3, 4)], {"axis": 1},
                            ref=(lambda r: lambda x: r(x, axis=1))(
                                getattr(np, _n)))
for _n, _r in (("amax", np.max), ("amin", np.min), ("nanmax", np.nanmax),
               ("nanmin", np.nanmin), ("ptp", np.ptp)):
    SPECS["_npi_" + _n] = S(lambda: [sep(3, 4)], {"axis": 1},
                            ref=(lambda r: lambda x: r(x, axis=1))(_r))
for _n in ("all", "any"):
    SPECS["_npi_" + _n] = S(lambda: [ints(3, 4, hi=2)], {"axis": 1},
                            ref=(lambda r: lambda x: r(x, axis=1))(
                                getattr(np, _n)))
SPECS["_npi_count_nonzero"] = S(lambda: [ints(3, 4, hi=2)], {"axis": 1},
                                ref=lambda x: np.count_nonzero(x, axis=1))
for _n in ("argmax", "argmin", "nanargmax", "nanargmin"):
    SPECS["_npi_" + _n] = S(lambda: [sep(3, 4)], {"axis": 1},
                            ref=(lambda r: lambda x: r(x, axis=1))(
                                getattr(np, _n)))
for _n in ("cumsum", "cumprod", "nancumsum", "nancumprod"):
    SPECS["_npi_" + _n] = S(lambda: [fpos(3, 4)], {"axis": 1},
                            ref=(lambda r: lambda x: r(x, axis=1))(
                                getattr(np, _n)))
SPECS["_npi_median"] = S(lambda: [sep(3, 5)], {"axis": 1},
                         ref=lambda x: np.median(x, axis=1))
SPECS["_npi_nanmedian"] = S(lambda: [sep(3, 5)], {"axis": 1},
                            ref=lambda x: np.nanmedian(x, axis=1))
SPECS["_npi_percentile"] = S(lambda: [sep(20)], {"q": 30.0},
                             ref=lambda x: np.percentile(x, 30.0),
                             grad=False)
SPECS["_npi_nanpercentile"] = S(lambda: [sep(20)], {"q": 30.0},
                                ref=lambda x: np.nanpercentile(x, 30.0),
                                grad=False)
SPECS["_npi_quantile"] = S(lambda: [sep(20)], {"q": 0.3},
                           ref=lambda x: np.quantile(x, 0.3), grad=False)
SPECS["_npi_nanquantile"] = S(lambda: [sep(20)], {"q": 0.3},
                              ref=lambda x: np.nanquantile(x, 0.3),
                              grad=False)
SPECS["_npi_average"] = S(lambda: [f(3, 4), fpos(3, 4)],
                          ref=lambda a, w: np.average(a, weights=w))
SPECS["_npi_trapz"] = S(lambda: [f(8)],
                        ref=lambda y: np.trapezoid(y)
                        if hasattr(np, "trapezoid") else np.trapz(y))

# shape manipulation
SPECS["_npi_reshape"] = S(lambda: [f(3, 4)], {"newshape": (4, 3)},
                          ref=lambda x: x.reshape(4, 3))
SPECS["_npi_ravel"] = S(lambda: [f(3, 4)], ref=np.ravel)
SPECS["_npi_transpose"] = S(lambda: [f(3, 4, 2)], {"axes": (2, 0, 1)},
                            ref=lambda x: x.transpose(2, 0, 1))
SPECS["_npi_swapaxes"] = S(lambda: [f(3, 4, 2)], {"axis1": 0, "axis2": 2},
                           ref=lambda x: np.swapaxes(x, 0, 2))
SPECS["_npi_moveaxis"] = S(lambda: [f(3, 4, 2)],
                           {"source": 0, "destination": 2},
                           ref=lambda x: np.moveaxis(x, 0, 2))
SPECS["_npi_rollaxis"] = S(lambda: [f(3, 4, 2)], {"axis": 2},
                           ref=lambda x: np.rollaxis(x, 2))
SPECS["_npi_expand_dims"] = S(lambda: [f(3, 4)], {"axis": 1},
                              ref=lambda x: np.expand_dims(x, 1))
SPECS["_npi_squeeze"] = S(lambda: [f(3, 1, 4)], {"axis": 1},
                          ref=lambda x: np.squeeze(x, 1))
SPECS["_npi_broadcast_to"] = S(lambda: [f(1, 4)], {"shape": (3, 4)},
                               ref=lambda x: np.broadcast_to(x, (3, 4)))
SPECS["_npi_flip"] = S(lambda: [f(3, 4)], {"axis": 1},
                       ref=lambda x: np.flip(x, 1))
SPECS["_npi_fliplr"] = S(lambda: [f(3, 4)], ref=np.fliplr)
SPECS["_npi_flipud"] = S(lambda: [f(3, 4)], ref=np.flipud)
SPECS["_npi_roll"] = S(lambda: [f(3, 4)], {"shift": 2, "axis": 1},
                       ref=lambda x: np.roll(x, 2, 1))
SPECS["_npi_rot90"] = S(lambda: [f(3, 4)], {"k": 1},
                        ref=lambda x: np.rot90(x, 1))
SPECS["_npi_concatenate"] = S(lambda: [f(3, 4), f(2, 4)], {"axis": 0},
                              ref=lambda a, b: np.concatenate([a, b], 0))
SPECS["_npi_stack"] = S(lambda: [f(3, 4), f(3, 4)], {"axis": 1},
                        ref=lambda a, b: np.stack([a, b], 1))
SPECS["_npi_column_stack"] = S(lambda: [f(4), f(4)],
                               ref=lambda a, b: np.column_stack([a, b]))
SPECS["_npi_hstack"] = S(lambda: [f(3, 4), f(3, 2)],
                         ref=lambda a, b: np.hstack([a, b]))
SPECS["_npi_vstack"] = S(lambda: [f(3, 4), f(2, 4)],
                         ref=lambda a, b: np.vstack([a, b]))
SPECS["_npi_dstack"] = S(lambda: [f(3, 4), f(3, 4)],
                         ref=lambda a, b: np.dstack([a, b]))
SPECS["_npi_split"] = S(lambda: [f(4, 6)],
                        {"indices_or_sections": 2, "axis": 1},
                        ref=lambda x: tuple(np.split(x, 2, 1)))
SPECS["_npi_array_split"] = S(lambda: [f(5, 4)],
                              {"indices_or_sections": 2, "axis": 0},
                              ref=lambda x: tuple(np.array_split(x, 2, 0)))
SPECS["_npi_hsplit"] = S(lambda: [f(4, 6)], {"indices_or_sections": 3},
                         ref=lambda x: tuple(np.hsplit(x, 3)))
SPECS["_npi_vsplit"] = S(lambda: [f(4, 6)], {"indices_or_sections": 2},
                         ref=lambda x: tuple(np.vsplit(x, 2)))
SPECS["_npi_dsplit"] = S(lambda: [f(2, 3, 4)], {"indices_or_sections": 2},
                         ref=lambda x: tuple(np.dsplit(x, 2)))
SPECS["_npi_repeat"] = S(lambda: [f(3, 4)], {"repeats": 2, "axis": 1},
                         ref=lambda x: np.repeat(x, 2, 1))
SPECS["_npi_tile"] = S(lambda: [f(3, 4)], {"reps": (2, 1)},
                       ref=lambda x: np.tile(x, (2, 1)))
SPECS["_npi_append"] = S(lambda: [f(3, 4), f(2, 4)], {"axis": 0},
                         ref=lambda a, b: np.append(a, b, 0))
SPECS["_npi_pad"] = S(lambda: [f(3, 4)], {"pad_width": ((1, 1), (2, 0))},
                      ref=lambda x: np.pad(x, ((1, 1), (2, 0))))
SPECS["_npi_delete"] = S(lambda: [f(5, 4)], {"obj": 2, "axis": 0},
                         ref=lambda x: np.delete(x, 2, 0))
SPECS["_npi_insert"] = S(lambda: [f(5, 4), f(1, 4)], {"obj": 2, "axis": 0},
                         ref=lambda x, v: np.insert(x, 2, v, 0))
SPECS["_npi_trim_zeros"] = S(
    lambda: [np.concatenate([[0.0, 0.0], fpos(4), [0.0]]).astype(np.float32)],
    ref=np.trim_zeros)

# indexing / selection
SPECS["_npi_take"] = S(lambda: [f(5, 4), ints(3, hi=5)], {"axis": 0},
                       ref=lambda x, i: np.take(x, i, 0))
SPECS["_npi_take_along_axis"] = S(
    lambda: [f(3, 4), np.argsort(R.rand(3, 4), 1).astype(np.int64)],
    {"axis": 1}, ref=lambda x, i: np.take_along_axis(x, i, 1))
SPECS["_npi_compress"] = S(lambda: [ints(4, hi=2), f(4, 3)], {"axis": 0},
                           ref=lambda c, x: np.compress(c.astype(bool), x, 0),
                           grad=False)
SPECS["_npi_extract"] = S(lambda: [ints(3, 4, hi=2), f(3, 4)],
                          ref=lambda c, x: np.extract(c, x), grad=False)
SPECS["_npi_choose"] = S(lambda: [ints(4, hi=3), f(4), f(4), f(4)],
                         ref=lambda i, a, b, c: np.choose(i, [a, b, c]))
SPECS["_npi_select"] = S(
    lambda: [ints(3, 4, hi=2), ints(3, 4, hi=2), f(3, 4), f(3, 4)],
    ref=lambda c1, c2, x1, x2: np.select([c1.astype(bool), c2.astype(bool)],
                                         [x1, x2]))
SPECS["_npi_where"] = S(lambda: [ints(3, 4, hi=2), f(3, 4), f(3, 4)],
                        ref=lambda c, x, y: np.where(c.astype(bool), x, y))
SPECS["_npi_nonzero"] = S(lambda: [ints(3, 4, hi=2)],
                          ref=lambda x: tuple(np.nonzero(x)), grad=False)
SPECS["_npi_flatnonzero"] = S(lambda: [ints(3, 4, hi=2)],
                              ref=np.flatnonzero, grad=False)
SPECS["_npi_argwhere"] = S(lambda: [ints(3, 4, hi=2)], ref=np.argwhere,
                           grad=False)
SPECS["_npi_searchsorted"] = S(lambda: [np.sort(f(8)), f(5)],
                               ref=np.searchsorted)
SPECS["_npi_unravel_index"] = S(lambda: [ints(5, hi=12)], {"shape": (3, 4)},
                                ref=lambda i: np.unravel_index(i, (3, 4)))
SPECS["_npi_ravel_multi_index"] = S(
    lambda: [ints(5, hi=3), ints(5, hi=4)], {"dims": (3, 4)},
    ref=lambda a, b: np.ravel_multi_index((a, b), (3, 4)))
SPECS["_npi_diag_indices_from"] = S(
    lambda: [f(4, 4)], ref=lambda x: tuple(np.diag_indices_from(x)),
    grad=False)
SPECS["_npi_tril_indices"] = S(lambda: [], {"n": 4, "k": 0},
                               ref=lambda: tuple(np.tril_indices(4)))
SPECS["_npi_triu_indices"] = S(lambda: [], {"n": 4, "k": 0},
                               ref=lambda: tuple(np.triu_indices(4)))
SPECS["_npi_indices"] = S(lambda: [], {"dimensions": (2, 3)},
                          ref=lambda: np.indices((2, 3)).astype(np.int32))

# linalg
SPECS["_npi_dot"] = S(lambda: [f(3, 4), f(4, 2)], ref=np.dot)
SPECS["_npi_vdot"] = S(lambda: [f(8), f(8)], ref=np.vdot)
SPECS["_npi_inner"] = S(lambda: [f(3, 4), f(2, 4)], ref=np.inner)
SPECS["_npi_outer"] = S(lambda: [f(3), f(4)], ref=np.outer)
SPECS["_npi_matmul"] = S(lambda: [f(2, 3, 4), f(2, 4, 5)], ref=np.matmul)
SPECS["_npi_tensordot"] = S(lambda: [f(3, 4, 5), f(4, 5, 2)],
                            {"axes": 2}, ref=lambda a, b: np.tensordot(a, b))
SPECS["_npi_trace"] = S(lambda: [f(4, 4)], ref=np.trace)

# set ops
SPECS["_npi_unique"] = S(lambda: [ints(12, hi=5)], ref=np.unique, grad=False)
SPECS["_npi_isin"] = S(lambda: [ints(3, 4), ints(5)], ref=np.isin)
SPECS["_npi_in1d"] = S(lambda: [ints(8), ints(5)],
                       ref=lambda a, b: np.isin(a.ravel(), b))
SPECS["_npi_intersect1d"] = S(lambda: [ints(8), ints(8)], ref=np.intersect1d,
                              grad=False)
SPECS["_npi_union1d"] = S(lambda: [ints(8), ints(8)], ref=np.union1d,
                          grad=False)
SPECS["_npi_setdiff1d"] = S(lambda: [ints(8), ints(8)], ref=np.setdiff1d,
                            grad=False)
SPECS["_npi_setxor1d"] = S(lambda: [ints(8), ints(8)], ref=np.setxor1d,
                           grad=False)

# sorting
SPECS["_npi_sort"] = S(lambda: [sep(3, 4)], {"axis": 1},
                       ref=lambda x: np.sort(x, 1))
SPECS["_npi_argsort"] = S(lambda: [sep(3, 4)], {"axis": 1},
                          ref=lambda x: np.argsort(x, 1))
SPECS["_npi_lexsort"] = S(lambda: [sep(6), sep(6)],
                          ref=lambda a, b: np.lexsort((a, b)))
# partition order within segments is UNSPECIFIED -> semantic test below,
# not an elementwise ref
SPECS["_npi_partition"] = S(lambda: [sep(8)], {"kth": 3}, grad=False)
SPECS["_npi_argpartition"] = S(lambda: [sep(8)], {"kth": 3}, grad=False)


def test_masked_log_softmax_partial():
    """Masked slots must be -inf and kept slots must renormalize over the
    kept set only (the battery spec uses an all-ones mask because its
    finiteness gate rejects -inf)."""
    x = f(3, 4)
    m = np.concatenate([np.ones((3, 1), np.int32),
                        ints(3, 3, lo=0, hi=2)], 1)
    got = invoke("masked_log_softmax", nd.array(x), nd.array(m),
                 axis=-1).asnumpy()
    want = _masked_log_softmax_ref(x, m)
    b = m.astype(bool)
    assert np.isneginf(got[~b]).all()
    assert_almost_equal(got[b], want[b], rtol=1e-4, atol=1e-4,
                        names=("masked_log_softmax", "ref"))


def test_fleet_update_writeback():
    """multi_* / preloaded_multi_* optimizer fleets write every output back
    in place (aux_writeback covers them all), so test_forward sees an empty
    visible return and compares nothing.  Pin the written-back weights
    against the update formulas here."""
    def arrs(*xs):
        return [nd.array(x) for x in xs]

    w1, g1, w2, g2 = f(4), f(4), f(3), f(3)
    ws = arrs(w1, w2)
    invoke("multi_sgd_update", ws[0], nd.array(g1), ws[1], nd.array(g2),
           lrs=(0.1, 0.2), wds=(0.0, 0.0), num_weights=2)
    assert_almost_equal(ws[0].asnumpy(), w1 - 0.1 * g1, 1e-5, 1e-5,
                        names=("multi_sgd w1", "ref"))
    assert_almost_equal(ws[1].asnumpy(), w2 - 0.2 * g2, 1e-5, 1e-5,
                        names=("multi_sgd w2", "ref"))

    # momentum variant, one step from zero state == plain sgd step
    ws = arrs(w1, w2)
    moms = arrs(np.zeros(4, np.float32), np.zeros(3, np.float32))
    invoke("multi_sgd_mom_update", ws[0], nd.array(g1), moms[0],
           ws[1], nd.array(g2), moms[1],
           lrs=(0.1, 0.1), wds=(0.0, 0.0), momentum=0.9, num_weights=2)
    assert_almost_equal(ws[0].asnumpy(), w1 - 0.1 * g1, 1e-5, 1e-5,
                        names=("multi_sgd_mom w1", "ref"))

    # mp variant: fp32 master weights drive the update
    ws = arrs(w1, w2)
    w32s = arrs(w1.copy(), w2.copy())
    invoke("multi_mp_sgd_update", ws[0], nd.array(g1), w32s[0],
           ws[1], nd.array(g2), w32s[1],
           lrs=(0.1, 0.1), wds=(0.0, 0.0), num_weights=2)
    assert_almost_equal(ws[0].asnumpy(), w1 - 0.1 * g1, 1e-5, 1e-5,
                        names=("multi_mp_sgd w1", "ref"))
    assert_almost_equal(w32s[1].asnumpy(), w2 - 0.1 * g2, 1e-5, 1e-5,
                        names=("multi_mp_sgd w32", "ref"))

    # preloaded variant: lrs/wds arrive as tensors
    ws = arrs(w1, w2)
    invoke("preloaded_multi_sgd_update", ws[0], nd.array(g1),
           ws[1], nd.array(g2),
           nd.array(np.array([0.1, 0.3], np.float32)),
           nd.array(np.zeros(2, np.float32)), num_weights=2)
    assert_almost_equal(ws[1].asnumpy(), w2 - 0.3 * g2, 1e-5, 1e-5,
                        names=("preloaded_multi_sgd w2", "ref"))

    # adamw fleet: one step from zero states vs the decoupled-AdamW formula
    m1, v1 = np.zeros(4, np.float32), np.zeros(4, np.float32)
    m2, v2 = np.zeros(3, np.float32), np.zeros(3, np.float32)
    ws = arrs(w1, w2)
    ms, vs = arrs(m1, m2), arrs(v1, v2)
    invoke("multi_adamw_update", ws[0], nd.array(g1), ms[0], vs[0],
           ws[1], nd.array(g2), ms[1], vs[1],
           nd.array(np.array(1.0, np.float32)),
           lrs=(0.01, 0.01), wds=(0.0, 0.0), num_weights=2)
    b1, b2, e = 0.9, 0.999, 1e-8
    nm, nv = (1 - b1) * g1, (1 - b2) * g1 * g1
    assert_almost_equal(ws[0].asnumpy(),
                        w1 - 0.01 * nm / (np.sqrt(nv) + e), 1e-5, 1e-5,
                        names=("multi_adamw w1", "ref"))


def test_npi_partition_semantics():
    x = sep(9)
    part = invoke("_npi_partition", nd.array(x), kth=4).asnumpy()
    api = invoke("_npi_argpartition", nd.array(x), kth=4).asnumpy()
    for out in (part, x[api]):
        assert out[4] == np.sort(x)[4]
        assert (out[:4] <= out[4]).all() and (out[5:] >= out[4]).all()
        assert sorted(out.tolist()) == sorted(x.tolist())
SPECS["_npi_msort"] = S(lambda: [sep(5, 3)], ref=lambda x: np.sort(x, 0))

# math misc
SPECS["_npi_clip"] = S(lambda: [f(3, 4)], {"a_min": -0.5, "a_max": 0.5},
                       ref=lambda x: np.clip(x, -0.5, 0.5))
SPECS["_npi_interp"] = S(lambda: [f(5), np.sort(f(8)), f(8)],
                         ref=np.interp, grad=False)
SPECS["_npi_ediff1d"] = S(lambda: [f(8)], ref=np.ediff1d)
SPECS["_npi_diff"] = S(lambda: [f(3, 6)], {"n": 1, "axis": 1},
                       ref=lambda x: np.diff(x, 1, 1))
SPECS["_npi_gradient"] = S(lambda: [f(4, 5)],
                           ref=lambda x: tuple(np.gradient(x)))
SPECS["_npi_convolve"] = S(lambda: [f(6), f(3)], {"mode": "full"},
                           ref=lambda a, v: np.convolve(a, v, "full"))
SPECS["_npi_correlate"] = S(lambda: [f(6), f(3)], {"mode": "valid"},
                            ref=lambda a, v: np.correlate(a, v, "valid"))
SPECS["_npi_polyval"] = S(lambda: [f(4), f(5)], ref=np.polyval)
SPECS["_npi_corrcoef"] = S(lambda: [f(3, 8)], ref=np.corrcoef, grad=False)
SPECS["_npi_cov"] = S(lambda: [f(3, 8)], ref=lambda m: np.cov(m),
                      grad=False)
SPECS["_npi_histogram"] = S(lambda: [f(20)], {"bins": 5, "range": (-1., 1.)},
                            ref=lambda x: np.histogram(x, 5, (-1., 1.)),
                            grad=False)
SPECS["_npi_bincount"] = S(lambda: [ints(12, hi=5)], ref=np.bincount,
                           grad=False)
SPECS["_npi_digitize"] = S(lambda: [f(8), np.sort(f(4))], ref=np.digitize)

# windows + creation
SPECS["_npi_bartlett"] = S(lambda: [], {"M": 8},
                           ref=lambda: np.bartlett(8), grad=False)
SPECS["_npi_kaiser"] = S(lambda: [], {"M": 8, "beta": 2.0},
                         ref=lambda: np.kaiser(8, 2.0), grad=False)
SPECS["_npi_blackman_np"] = S(lambda: [], {"M": 8},
                              ref=lambda: np.blackman(8), grad=False)
SPECS["_npi_hamming_np"] = S(lambda: [], {"M": 8},
                             ref=lambda: np.hamming(8), grad=False)
SPECS["_npi_hanning_np"] = S(lambda: [], {"M": 8},
                             ref=lambda: np.hanning(8), grad=False)
SPECS["_npi_full_like"] = S(lambda: [f(3, 4)], {"fill_value": 2.5},
                            ref=lambda x: np.full_like(x, 2.5))
SPECS["_npi_empty_like"] = S(lambda: [f(3, 4)], grad=False)  # values undef
SPECS["_npi_identity"] = S(lambda: [], {"n": 4},
                           ref=lambda: np.identity(4, np.float32))
SPECS["_npi_tri"] = S(lambda: [], {"N": 4, "k": 0},
                      ref=lambda: np.tri(4, dtype=np.float32))
SPECS["_npi_diagflat"] = S(lambda: [f(4)], {"k": 1},
                           ref=lambda x: np.diagflat(x, 1))
SPECS["_npi_vander"] = S(lambda: [f(4)], {"N": 3},
                         ref=lambda x: np.vander(x, 3))
SPECS["_npi_meshgrid"] = S(lambda: [f(3), f(4)],
                           ref=lambda a, b: tuple(np.meshgrid(a, b)))
SPECS["_npi_broadcast_arrays"] = S(
    lambda: [f(1, 4), f(3, 1)],
    ref=lambda a, b: tuple(np.broadcast_arrays(a, b)))
SPECS["_npi_logspace"] = S(lambda: [], {"start": 0.0, "stop": 2.0, "num": 5},
                           ref=lambda: np.logspace(0.0, 2.0, 5), grad=False)
SPECS["_npi_geomspace"] = S(lambda: [], {"start": 1.0, "stop": 16.0,
                                         "num": 5},
                            ref=lambda: np.geomspace(1.0, 16.0, 5),
                            grad=False)

# numpy linalg (_npi_*): deterministic factorizations get direct refs;
# sign/order-ambiguous ones (svd/qr/eigh/lstsq) are pinned by the
# reconstruction-identity test below
SPECS["_npi_solve"] = S(lambda: [_spd(4), f(4, 2)],
                        ref=np.linalg.solve, rtol=1e-3, atol=1e-3)
SPECS["_npi_pinv"] = S(lambda: [f(4, 3)], ref=np.linalg.pinv,
                       rtol=1e-3, atol=1e-3)
SPECS["_npi_cholesky"] = S(lambda: [_spd(4)], ref=np.linalg.cholesky,
                           rtol=1e-3, atol=1e-3)
SPECS["_npi_eigvalsh"] = S(lambda: [_spd(4)], ref=np.linalg.eigvalsh,
                           rtol=1e-3, atol=1e-3)
SPECS["_npi_matrix_rank"] = S(lambda: [_spd(4)], grad=False,
                              ref=lambda a: np.asarray(
                                  np.linalg.matrix_rank(a)))
SPECS["_npi_matrix_power"] = S(lambda: [_spd(3)], {"n": 3},
                               ref=lambda a: np.linalg.matrix_power(a, 3),
                               rtol=1e-3, atol=1e-3)
SPECS["_npi_multi_dot"] = S(lambda: [f(3, 4), f(4, 5), f(5, 2)],
                            ref=lambda *ms: np.linalg.multi_dot(ms))
SPECS["_npi_tensorsolve"] = S(
    lambda: [_spd(4).reshape(2, 2, 2, 2), f(2, 2)],
    ref=np.linalg.tensorsolve, rtol=1e-3, atol=1e-3, grad=False)
SPECS["_npi_tensorinv"] = S(lambda: [_spd(4).reshape(2, 2, 2, 2)],
                            ref=np.linalg.tensorinv,
                            rtol=1e-3, atol=1e-3, grad=False)
SPECS["_npi_cond"] = S(lambda: [_spd(4)], grad=False,
                       ref=lambda a: np.asarray(np.linalg.cond(a),
                                                np.float32),
                       rtol=1e-3, atol=1e-3)
SPECS["_npi_svd"] = S(lambda: [f(4, 3)], grad=False)     # sign-ambiguous
SPECS["_npi_qr"] = S(lambda: [f(4, 3)], grad=False)      # sign-ambiguous
SPECS["_npi_eigh"] = S(lambda: [_spd(4)], grad=False)    # sign-ambiguous
SPECS["_npi_lstsq"] = S(lambda: [f(5, 3), f(5, 2)], grad=False)


def test_npi_linalg_reconstruction_identities():
    """svd/qr/eigh/lstsq are unique only up to signs/order: pin them by
    the identities they must satisfy instead of elementwise refs."""
    a = f(5, 3)
    u, s, vh = (x.asnumpy() for x in invoke("_npi_svd", nd.array(a)))
    np.testing.assert_allclose((u * s) @ vh, a, rtol=1e-4, atol=1e-4)
    q, r = (x.asnumpy() for x in invoke("_npi_qr", nd.array(a)))
    np.testing.assert_allclose(q @ r, a, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(q.T @ q, np.eye(3), rtol=1e-4, atol=1e-4)
    spd = _spd(4)
    w, v = (x.asnumpy() for x in invoke("_npi_eigh", nd.array(spd)))
    np.testing.assert_allclose(v @ np.diag(w) @ v.T, spd,
                               rtol=1e-3, atol=1e-3)
    A, b = f(6, 3), f(6, 2)
    x = invoke("_npi_lstsq", nd.array(A), nd.array(b))[0].asnumpy()
    want = np.linalg.lstsq(A, b, rcond=None)[0]
    np.testing.assert_allclose(x, want, rtol=1e-3, atol=1e-3)


# numpy-era + *_like samplers: stochastic -> shape/finiteness + moments
for _n, _p in [
        ("_random_uniform_like", {}), ("_random_normal_like", {}),
        ("_random_exponential_like", {}), ("_random_gamma_like", {}),
        ("_random_poisson_like", {}), ("_random_negative_binomial_like", {}),
        ("_random_generalized_negative_binomial_like", {})]:
    SPECS[_n] = S(lambda: [fpos(64)], _p, grad=False)
for _n, _p in [
        ("_npi_uniform", {"size": (64,)}), ("_npi_normal", {"size": (64,)}),
        ("_npi_laplace", {"size": (64,)}), ("_npi_beta", {"size": (64,)}),
        ("_npi_chisquare", {"size": (64,)}), ("_npi_f", {"size": (64,)}),
        ("_npi_standard_t", {"df": 4.0, "size": (64,)}),
        ("_npi_lognormal", {"size": (64,)}),
        ("_npi_triangular", {"size": (64,)})]:
    SPECS[_n] = S(lambda: [], _p, grad=False)
SPECS["_npi_choice"] = S(lambda: [fpos(16)], {"size": (8,)}, grad=False)
SPECS["_npi_permutation"] = S(lambda: [f(8)], grad=False)


# Ops exercised by dedicated suites rather than the battery:
# _npi scalar-variant family (generated, mirroring the kernel table):
# every entry carries an independent numpy ref.  Int-domain ops use int
# inputs + is_int=True.
def _ints34():
    return ints(3, 4, hi=7) + 1


_SCALAR_FAM = {
    # name: (inputs, scalar, np forward, int_domain)
    "add": (lambda: [f(3, 4)], 1.7, np.add, False),
    "subtract": (lambda: [f(3, 4)], 1.7, np.subtract, False),
    "multiply": (lambda: [f(3, 4)], 1.7, np.multiply, False),
    "true_divide": (lambda: [f(3, 4)], 1.7, np.true_divide, False),
    "power": (lambda: [fpos(3, 4)], 1.3, np.power, False),
    "float_power": (lambda: [fpos(3, 4)], 1.3, np.float_power, False),
    "arctan2": (lambda: [f(3, 4)], 0.7, np.arctan2, False),
    "hypot": (lambda: [f(3, 4)], 0.7, np.hypot, False),
    "logaddexp": (lambda: [f(3, 4)], 0.7, np.logaddexp, False),
    "logaddexp2": (lambda: [f(3, 4)], 0.7, np.logaddexp2, False),
    "maximum": (lambda: [f(3, 4)], 0.3, np.maximum, False),
    "minimum": (lambda: [f(3, 4)], 0.3, np.minimum, False),
    "fmax": (lambda: [f(3, 4)], 0.3, np.fmax, False),
    "fmin": (lambda: [f(3, 4)], 0.3, np.fmin, False),
    "copysign": (lambda: [f(3, 4)], -1.0, np.copysign, False),
    "floor_divide": (lambda: [fpos(3, 4)], 0.7, np.floor_divide, False),
    "mod": (lambda: [fpos(3, 4)], 0.7, np.mod, False),
    "fmod": (lambda: [fpos(3, 4)], 0.7, np.fmod, False),
    "nextafter": (lambda: [f(3, 4)], 1.0, np.nextafter, False),
    "ldexp": (lambda: [f(3, 4)], 2.0,
              lambda x, s: np.ldexp(x, int(s)), True),
    "heaviside": (lambda: [f(3, 4)], 0.5, np.heaviside, False),
    "gcd": (lambda: [_ints34()], 6.0,
            lambda x, s: np.gcd(x, int(s)), True),
    "lcm": (lambda: [_ints34()], 6.0,
            lambda x, s: np.lcm(x, int(s)), True),
    "bitwise_and": (lambda: [_ints34()], 6.0,
                    lambda x, s: np.bitwise_and(x, int(s)), True),
    "bitwise_or": (lambda: [_ints34()], 6.0,
                   lambda x, s: np.bitwise_or(x, int(s)), True),
    "bitwise_xor": (lambda: [_ints34()], 6.0,
                    lambda x, s: np.bitwise_xor(x, int(s)), True),
    "left_shift": (lambda: [_ints34()], 2.0,
                   lambda x, s: np.left_shift(x, int(s)), True),
    "right_shift": (lambda: [_ints34()], 1.0,
                    lambda x, s: np.right_shift(x, int(s)), True),
    "equal": (lambda: [ints(3, 4, hi=3).astype(np.float32)], 1.0,
              np.equal, False),
    "not_equal": (lambda: [ints(3, 4, hi=3).astype(np.float32)], 1.0,
                  np.not_equal, False),
    "less": (lambda: [f(3, 4)], 0.0, np.less, False),
    "less_equal": (lambda: [f(3, 4)], 0.0, np.less_equal, False),
    "greater": (lambda: [f(3, 4)], 0.0, np.greater, False),
    "greater_equal": (lambda: [f(3, 4)], 0.0, np.greater_equal, False),
    "logical_and": (lambda: [ints(3, 4, hi=2).astype(np.float32)], 1.0,
                    np.logical_and, False),
    "logical_or": (lambda: [ints(3, 4, hi=2).astype(np.float32)], 0.0,
                   np.logical_or, False),
    "logical_xor": (lambda: [ints(3, 4, hi=2).astype(np.float32)], 1.0,
                    np.logical_xor, False),
}

_R_SCALAR = ("subtract", "true_divide", "power", "mod", "floor_divide",
             "arctan2", "copysign", "ldexp")


def _mk_scalar_spec(np_fn, scalar, refl, int_dom):
    if refl:
        ref = lambda x: np.asarray(np_fn(  # noqa: E731
            (int(scalar) if int_dom else scalar), x))
    else:
        ref = lambda x: np.asarray(np_fn(  # noqa: E731
            x, (int(scalar) if int_dom else scalar)))
    return ref


# the differentiable subset gets the numeric-gradient battery too
# (random float inputs stay clear of the max/min/copysign kinks)
_SCALAR_DIFF = {"add", "subtract", "multiply", "true_divide", "power",
                "float_power", "arctan2", "hypot", "logaddexp",
                "logaddexp2", "maximum", "minimum", "fmax", "fmin",
                "copysign"}

for _n, (_inp, _s, _np_fn, _intd) in _SCALAR_FAM.items():
    _params = {"scalar": _s}
    if _intd:
        _params["is_int"] = True
    _g = _n in _SCALAR_DIFF
    SPECS["_npi_%s_scalar" % _n] = S(
        _inp, dict(_params), grad=_g,
        ref=_mk_scalar_spec(_np_fn, _s, False, _intd))
    if _n in _R_SCALAR and _n != "ldexp":
        SPECS["_npi_r%s_scalar" % _n] = S(
            _inp, dict(_params), grad=_g,
            ref=_mk_scalar_spec(_np_fn, _s, True, _intd))

# reflected ldexp: scalar * 2**data, float exponents allowed
SPECS["_npi_rldexp_scalar"] = S(
    lambda: [f(3, 4)], {"scalar": 2.0},
    ref=lambda x: np.asarray(2.0 * np.exp2(x)))
SPECS["_npi_rnextafter_scalar"] = S(
    lambda: [f(3, 4)], {"scalar": 1.0}, grad=False,
    ref=lambda x: np.nextafter(np.float32(1.0), x))

SPECS.update({
    "_npi_mod": S(lambda: [fpos(3, 4), fpos(3, 4) + 0.5], grad=False,
                  ref=np.mod),
    "_npi_rarctan2": S(lambda: [f(3, 4), f(3, 4)],
                       ref=lambda a, b: np.arctan2(b, a)),
    "_npi_rcopysign": S(lambda: [f(3, 4), f(3, 4)],
                        ref=lambda a, b: np.copysign(b, a)),
    "_npi_rldexp": S(lambda: [f(3, 4), f(3, 4)],
                     ref=lambda a, b: np.asarray(b * np.exp2(a))),
    "_npi_spacing": S(lambda: [f(3, 4)], grad=False, ref=np.spacing),
    "_npx_nonzero": S(lambda: [ints(3, 4, hi=2).astype(np.float32)],
                      grad=False,
                      ref=lambda x: np.stack(np.nonzero(x), axis=-1)),
})


def _lamb_ref(w, g, m, v, lr, wd, beta1=0.9, beta2=0.999, eps=1e-6, t=1):
    """NumPy LAMB single step: adam moments, one trust ratio on the whole
    update (incl. weight decay)."""
    m1 = beta1 * m + (1 - beta1) * g
    v1 = beta2 * v + (1 - beta2) * g * g
    mh = m1 / (1 - beta1 ** t)
    vh = v1 / (1 - beta2 ** t)
    upd = mh / (np.sqrt(vh) + eps) + wd * w
    wn = np.sqrt(np.sum(w * w))
    un = np.sqrt(np.sum(upd * upd))
    ratio = wn / un if wn > 0 and un > 0 else 1.0
    return (w - lr * ratio * upd, m1, v1)


def _rroi_ref(data, rois, PH=2, PW=2, S=2):
    """NumPy rotated-roi-align (angle=0 case exercises the full bilinear
    sampling path)."""
    N = rois.shape[0]
    C = data.shape[1]
    out = np.zeros((N, C, PH, PW), np.float32)
    H, W = data.shape[2], data.shape[3]
    for n in range(N):
        b, cx, cy, rw, rh, ang = rois[n]
        rw, rh = max(rw, 1.0), max(rh, 1.0)
        th = ang * np.pi / 180.0
        ix = (np.arange(S) + 0.5) / S
        lx = (((np.arange(PW)[:, None] + ix) / PW) - 0.5).reshape(-1) * rw
        ly = (((np.arange(PH)[:, None] + ix) / PH) - 0.5).reshape(-1) * rh
        gx, gy = np.meshgrid(lx, ly, indexing="xy")
        sx = cx + gx * np.cos(th) - gy * np.sin(th)
        sy = cy + gx * np.sin(th) + gy * np.cos(th)
        x0 = np.clip(np.floor(sx).astype(int), 0, W - 1)
        y0 = np.clip(np.floor(sy).astype(int), 0, H - 1)
        x1 = np.clip(x0 + 1, 0, W - 1)
        y1 = np.clip(y0 + 1, 0, H - 1)
        fx = np.clip(sx, 0, W - 1) - x0
        fy = np.clip(sy, 0, H - 1) - y0
        img = data[int(b)]
        vals = (img[:, y0, x0] * (1 - fx) * (1 - fy)
                + img[:, y0, x1] * fx * (1 - fy)
                + img[:, y1, x0] * (1 - fx) * fy
                + img[:, y1, x1] * fx * fy)
        out[n] = vals.reshape(C, PH, S, PW, S).mean(axis=(2, 4))
    return out


def _slice_assign_ref(lhs, rhs, begin, end):
    out = lhs.copy()
    out[tuple(slice(b, e) for b, e in zip(begin, end))] = rhs
    return out


def _index_copy_ref(old, idx, new):
    out = old.copy()
    out[idx.astype(int)] = new
    return out


SPECS.update({
    "adagrad_update": S(
        lambda: [f(4), f(4), fpos(4)], {"lr": 0.01, "wd": 0.01},
        grad=False,
        ref=lambda w, g, h: w - 0.01 * (
            g / np.sqrt(h + g * g + 1e-7) + 0.01 * w)),
    "multi_lamb_update": S(
        lambda: [f(4), f(4), np.zeros(4, np.float32),
                 np.zeros(4, np.float32)],
        {"learning_rates": (0.1,), "wds": (0.01,), "t": 1,
         "num_weights": 1}, grad=False,
        ref=lambda w, g, m, v: _lamb_ref(w, g, m, v, 0.1, 0.01)),
    "multi_mp_lamb_update": S(
        lambda: [_MPLANS_W.copy(), f(4), np.zeros(4, np.float32),
                 np.zeros(4, np.float32), _MPLANS_W.astype(np.float32)],
        {"learning_rates": (0.1,), "wds": (0.01,), "t": 1,
         "num_weights": 1}, grad=False,
        ref=lambda w, g, m, v, w32: _lamb_ref(w32, g, m, v, 0.1, 0.01)),
    "_contrib_boolean_mask": S(
        lambda: [f(4, 3), np.array([1, 0, 1, 1], np.float32)], {},
        grad=False,
        ref=lambda d, i: d[i != 0]),
    "_contrib_index_copy": S(
        lambda: [f(5, 3), np.array([0, 2], np.int32), f(2, 3)], {},
        ref=lambda o, i, n: _index_copy_ref(o, i, n)),
    "_identity_with_attr_like_rhs": S(
        lambda: [f(3, 4), f(3, 4)], {}, ref=lambda a, b: a),
    "_slice_assign": S(
        lambda: [f(4, 5), f(2, 4)], {"begin": (1, 0), "end": (3, 4)},
        ref=lambda l, r: _slice_assign_ref(l, r, (1, 0), (3, 4))),
    "_slice_assign_scalar": S(
        lambda: [f(4, 5)], {"scalar": 2.5, "begin": (1, 0), "end": (3, 4)},
        ref=lambda l: _slice_assign_ref(
            l, np.float32(2.5), (1, 0), (3, 4))),
    "_contrib_RROIAlign": S(
        lambda: [f(1, 2, 8, 8),
                 np.array([[0, 4.0, 4.0, 4.0, 4.0, 30.0]], np.float32)],
        {"pooled_size": (2, 2), "spatial_scale": 1.0, "sampling_ratio": 2},
        grad=False,
        ref=lambda d, r: _rroi_ref(d, r)),
})


TESTED_ELSEWHERE = {
    # round-5 numpy-surface families: oracled in tests/test_numpy_extras.py
    **{op: "tests/test_numpy_extras.py" for op in (
        "_npi_fft", "_npi_ifft", "_npi_rfft", "_npi_irfft", "_npi_hfft",
        "_npi_ihfft", "_npi_fft2", "_npi_ifft2", "_npi_rfft2",
        "_npi_irfft2", "_npi_fftn", "_npi_ifftn", "_npi_rfftn",
        "_npi_irfftn", "_npi_fftfreq", "_npi_rfftfreq", "_npi_fftshift",
        "_npi_ifftshift",
        "_npi_polyadd", "_npi_polysub", "_npi_polymul", "_npi_polydiv",
        "_npi_polyder", "_npi_polyint", "_npi_polyfit", "_npi_roots",
        "_npi_poly", "_npi_kaiser", "_npi_unwrap", "_npi_spacing",
        "_npi_histogram_bin_edges", "_npi_real_if_close",
        "_npi_matrix_transpose", "_npi_place_impl", "_npi_putmask_impl",
        "_npi_dirichlet", "_npi_standard_cauchy", "_npi_standard_gamma",
        "_npi_noncentral_chisquare", "_npi_wald", "_npi_logseries",
        "_npi_vonmises", "_npi_zipf",
        "_npx_betainc", "_npx_zeta", "_npx_ndtr", "_npx_ndtri",
        "_npx_log_ndtr", "_npx_logit", "_npx_expit", "_npx_xlogy",
        "_npx_xlog1py", "_npx_entr", "_npx_rel_entr", "_npx_kl_div",
        "_npx_i0e", "_npx_i1", "_npx_i1e", "_npx_betaln",
        "_npx_bernoulli", "_npx_expi", "_npx_expn", "_npx_exp1",
        "_npx_factorial", "_npx_gammasgn", "_npx_hyp1f1",
        "_npx_multigammaln", "_npx_poch", "_npx_spence",
        "_npx_stats_norm_pdf", "_npx_stats_norm_logpdf",
        "_npx_stats_norm_cdf", "_npx_stats_norm_logcdf",
        "_npx_stats_expon_logpdf", "_npx_stats_gamma_logpdf",
        "_npx_stats_beta_logpdf", "_npx_stats_t_logpdf",
        "_npx_stats_cauchy_logpdf", "_npx_stats_laplace_logpdf",
        "_npx_stats_uniform_logpdf", "_npx_stats_poisson_pmf",
        "_npx_stats_poisson_logpmf", "_npx_stats_bernoulli_logpmf",
    )},
    "_contrib_quantize": "tests/test_quantization.py",
    "_contrib_quantize_v2": "tests/test_quantization.py",
    "_contrib_dequantize": "tests/test_quantization.py",
    "_contrib_requantize": "tests/test_quantization.py",
    "_contrib_quantized_fully_connected": "tests/test_quantization.py",
    "_contrib_quantized_conv": "tests/test_quantization.py",
    "_contrib_quantized_pooling": "tests/test_quantization.py",
    "_contrib_quantized_flatten": "tests/test_quantization.py",
    "_contrib_quantized_act": "tests/test_quantization.py",
    "LinearRegressionOutput": "tests/test_module.py",
    "MAERegressionOutput": "tests/test_module.py",
    "LogisticRegressionOutput": "tests/test_module.py",
    "_sparse_sgd_update": "tests/test_sparse.py",
    "_sparse_sgd_mom_update": "tests/test_sparse.py",
    "_sparse_adam_update": "tests/test_sparse.py",
    "RNN": "tests/test_rnn.py",
    "CTCLoss": "tests/test_loss.py",
    "multi_head_attention": "tests/test_transformer.py",
    "moe_token_choice": "tests/test_glm_moe_lite.py",
    "moe_topk_choice": "tests/test_glm_moe_lite.py",
    "_contrib_interleaved_matmul_selfatt_qk": "tests/test_transformer.py",
    "_contrib_interleaved_matmul_selfatt_valatt": "tests/test_transformer.py",
    "_contrib_interleaved_matmul_encdec_qk": "tests/test_transformer.py",
    "_contrib_interleaved_matmul_encdec_valatt": "tests/test_transformer.py",
    "sgd_update": "tests/test_optimizer.py",
    "sgd_mom_update": "tests/test_optimizer.py",
    "mp_sgd_update": "tests/test_optimizer.py",
    "mp_sgd_mom_update": "tests/test_optimizer.py",
    "adam_update": "tests/test_optimizer.py",
    "adamw_update": "tests/test_optimizer.py",
    "nag_mom_update": "tests/test_optimizer.py",
    "rmsprop_update": "tests/test_optimizer.py",
    "rmspropalex_update": "tests/test_optimizer.py",
    "ftrl_update": "tests/test_optimizer.py",
    "signsgd_update": "tests/test_optimizer.py",
    "signum_update": "tests/test_optimizer.py",
    "lamb_update_phase1": "tests/test_optimizer.py",
    "lamb_update_phase2": "tests/test_optimizer.py",
    "rrelu": "stochastic activation (forward sanity only via LeakyReLU)",
    "_internal_getitem": "tests/test_ndarray.py (indexing suite)",
    "_contrib_dgl_adjacency": "tests/test_graph.py",
    "_contrib_dgl_subgraph": "tests/test_graph.py",
    "_contrib_dgl_csr_neighbor_uniform_sample": "tests/test_graph.py",
    "_contrib_dgl_csr_neighbor_non_uniform_sample": "tests/test_graph.py",
    "_contrib_dgl_graph_compact": "tests/test_graph.py",
}


def _unique_ops():
    seen = {}
    for name in registry.list_ops():
        op = registry.get_op(name)
        seen.setdefault(id(op), op.name)
    return sorted(seen.values())


def test_coverage():
    missing = [op for op in _unique_ops()
               if op not in SPECS and op not in TESTED_ELSEWHERE]
    assert not missing, ("ops without battery spec or TESTED_ELSEWHERE "
                         "entry: %s" % missing)


@pytest.mark.parametrize("opname", sorted(SPECS))
def test_forward(opname):
    spec = SPECS[opname]
    np_inputs = spec.inputs()
    nd_inputs = [nd.array(x) for x in np_inputs]
    out = invoke(opname, *nd_inputs, **spec.params)
    outs = out if isinstance(out, (list, tuple)) else [out]
    for o in outs:
        a = o.asnumpy()
        assert a.shape is not None
        if np.issubdtype(a.dtype, np.floating):
            assert np.isfinite(a).all(), "%s produced non-finite" % opname
    if spec.ref is not None:
        expect = spec.ref(*np_inputs)
        expects = expect if isinstance(expect, tuple) else (expect,)
        for o, e in zip(outs, expects):
            assert_almost_equal(o.asnumpy(), np.asarray(e),
                                rtol=spec.rtol, atol=spec.atol,
                                names=(opname, opname + "_ref"))


def _grad_specs():
    out = []
    for opname in sorted(SPECS):
        spec = SPECS[opname]
        op = registry.get_op(opname)
        do_grad = spec.grad if spec.grad is not None else op.differentiable
        if not do_grad:
            continue
        np_inputs = spec.inputs()
        if not np_inputs or any(not np.issubdtype(x.dtype, np.floating)
                                for x in np_inputs):
            continue
        out.append(opname)
    return out


@pytest.mark.parametrize("opname", _grad_specs())
def test_grad(opname):
    spec = SPECS[opname]
    np_inputs = spec.inputs()
    nd_inputs = [nd.array(x) for x in np_inputs]

    def fn(*args):
        out = invoke(opname, *args, **spec.params)
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out

    check_numeric_gradient(fn, nd_inputs, rtol=spec.grad_rtol,
                           atol=spec.grad_atol)


# Keywords an operator gained after its spec was written: (operator, spec)
# under a name of their own, so the battery's one spec an operator stays
# what it was.  `rotary_embedding`: the FIRST lanes of a head turn (a
# partial rotary factor), YaRN's blended frequencies (2 pairs, the ramp
# between them) and its attention factor.  `multi_head_attention`: a
# causal call's band of `window` keys, on 3 query heads a key/value head.
def _rotary_first_ref(x, inv, factor=1.0):
    """4 heads' first 4 lanes turned by `inv` (2 pairs), the rest kept."""
    h = x.reshape(2, 5, 2, 6)
    angle = np.arange(5)[:, None] * np.asarray(inv)
    cos, sin = (fn(angle)[None, :, None, :] * factor
                for fn in (np.cos, np.sin))
    a, b = h[..., :2], h[..., 2:4]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin, h[..., 4:]],
                          -1).reshape(2, 5, 12).astype(np.float32)


def _yarn_ref(x):
    # d 4, theta 100, factor 8, original 8: dim(n) = 4 ln(8 / (2 pi n)) /
    # (2 ln 100); beta_fast 2 -> low 0 (dim < 0), beta_slow 0.1 -> high 1
    f = np.array([1.0, 100.0 ** -0.5])
    low = max(np.floor(4 * np.log(8 / (2 * np.pi * 2)) / (2 * np.log(100))),
              0)
    high = min(np.ceil(4 * np.log(8 / (2 * np.pi * 0.1))
                       / (2 * np.log(100))), 3)
    ramp = np.clip((np.arange(2) - low) / (high - low), 0, 1)
    return _rotary_first_ref(x, (1 - ramp) * f + ramp * f / 8, 1.2)


def _band_ref(q, k, v):
    qh = q.reshape(2, 6, 3, 4).transpose(0, 2, 1, 3)
    s = np.einsum("bhqd,bkd->bhqk", qh, k) / 2.0
    i, j = np.arange(6)[:, None], np.arange(6)[None, :]
    s = np.where((j <= i) & (j > i - 2), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    out = np.einsum("bhqk,bkd->bhqd", p / p.sum(-1, keepdims=True), v)
    return out.transpose(0, 2, 1, 3).reshape(2, 6, 12).astype(np.float32)


KEYWORD_SPECS = {
    "rotary_embedding[first]": ("rotary_embedding", S(
        lambda: _own_draw((2, 5, 12)),
        params={"num_heads": 2, "rotary_dim": 4, "theta": 100.0,
                "first": True},
        ref=lambda x: _rotary_first_ref(x, [1.0, 0.1]))),
    "rotary_embedding[yarn]": ("rotary_embedding", S(
        lambda: _own_draw((2, 5, 12)),
        params={"num_heads": 2, "rotary_dim": 4, "theta": 100.0,
                "first": True, "yarn": (8.0, 8.0, 2.0, 0.1),
                "attention_factor": 1.2}, ref=_yarn_ref)),
    "multi_head_attention[window]": ("multi_head_attention", S(
        lambda: _own_draw((2, 6, 12), (2, 6, 4), (2, 6, 4)),
        params={"num_heads": 3, "causal": True, "window": 2},
        ref=_band_ref)),
}


@pytest.mark.parametrize("name", sorted(KEYWORD_SPECS))
def test_forward_keywords(name):
    opname, spec = KEYWORD_SPECS[name]
    np_inputs = spec.inputs()
    out = invoke(opname, *[nd.array(x) for x in np_inputs], **spec.params)
    assert_almost_equal(out.asnumpy(), spec.ref(*np_inputs), rtol=spec.rtol,
                        atol=spec.atol, names=(name, name + "_ref"))


@pytest.mark.parametrize("name", sorted(KEYWORD_SPECS))
def test_grad_keywords(name):
    opname, spec = KEYWORD_SPECS[name]
    check_numeric_gradient(
        lambda *args: invoke(opname, *args, **spec.params),
        [nd.array(x) for x in spec.inputs()], rtol=spec.grad_rtol,
        atol=spec.grad_atol)


def test_ste_identity_gradient():
    """round_ste/sign_ste must pass the incoming gradient straight through
    (reference: stes_op.cc)."""
    from mxnet_tpu import autograd
    for op in ("_contrib_round_ste", "_contrib_sign_ste"):
        x = nd.array(f(3, 4))
        x.attach_grad()
        with autograd.record():
            y = invoke(op, x)
        y.backward(nd.array(np.full((3, 4), 2.5, np.float32)))
        np.testing.assert_allclose(x.grad.asnumpy(),
                                   np.full((3, 4), 2.5), rtol=1e-6)


def test_multi_lans_matches_reference():
    """Fleet outputs are written back in place (visible return is empty),
    so the in-place results must be compared explicitly against the numpy
    LANS step — including a NONZERO weight decay inside both trust terms."""
    w_np, g_np = f(4), f(4)
    w = nd.array(w_np)
    g = nd.array(g_np)
    m = nd.array(np.zeros(4, np.float32))
    v = nd.array(np.zeros(4, np.float32))
    invoke("multi_lans_update", w, g, m, v,
           learning_rates=(0.1,), wds=(0.01,), t=1, num_weights=1)
    w_ref, m_ref, v_ref = _lans_ref(w_np, g_np, np.zeros(4, np.float32),
                                    np.zeros(4, np.float32), 0.1, 0.01)
    np.testing.assert_allclose(w.asnumpy(), w_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m.asnumpy(), m_ref, rtol=1e-5)
    np.testing.assert_allclose(v.asnumpy(), v_ref, rtol=1e-5, atol=1e-9)

    # mixed-precision variant: master weights drive the math
    w2_np = f(4)
    w2 = nd.array(w2_np.astype(np.float32))
    g2_np = f(4)
    g2 = nd.array(g2_np)
    m2 = nd.array(np.zeros(4, np.float32))
    v2 = nd.array(np.zeros(4, np.float32))
    w32 = nd.array(w2_np.astype(np.float32))
    invoke("multi_mp_lans_update", w2, g2, m2, v2, w32,
           learning_rates=(0.1,), wds=(0.01,), t=1, num_weights=1)
    wr, mr, vr = _lans_ref(w2_np, g2_np, np.zeros(4, np.float32),
                           np.zeros(4, np.float32), 0.1, 0.01)
    np.testing.assert_allclose(w32.asnumpy(), wr, rtol=1e-5, atol=1e-6)


def test_multi_lamb_matches_reference():
    """LAMB fleet outputs are in-place (visible return empty) — compare the
    written-back arrays against the numpy LAMB step, nonzero weight decay.
    (The SPECS refs for these two ops never execute for the same reason;
    this test is the real comparison.)"""
    w_np, g_np = f(4), f(4)
    w, g = nd.array(w_np), nd.array(g_np)
    m = nd.array(np.zeros(4, np.float32))
    v = nd.array(np.zeros(4, np.float32))
    invoke("multi_lamb_update", w, g, m, v,
           learning_rates=(0.1,), wds=(0.01,), t=1, num_weights=1)
    w_ref, m_ref, v_ref = _lamb_ref(w_np, g_np, np.zeros(4, np.float32),
                                    np.zeros(4, np.float32), 0.1, 0.01)
    np.testing.assert_allclose(w.asnumpy(), w_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m.asnumpy(), m_ref, rtol=1e-5)
    np.testing.assert_allclose(v.asnumpy(), v_ref, rtol=1e-5, atol=1e-9)

    w2_np, g2_np = f(4), f(4)
    w2 = nd.array(w2_np)
    g2 = nd.array(g2_np)
    m2 = nd.array(np.zeros(4, np.float32))
    v2 = nd.array(np.zeros(4, np.float32))
    w32 = nd.array(w2_np.astype(np.float32))
    invoke("multi_mp_lamb_update", w2, g2, m2, v2, w32,
           learning_rates=(0.1,), wds=(0.01,), t=1, num_weights=1)
    wr, mr, vr = _lamb_ref(w2_np, g2_np, np.zeros(4, np.float32),
                           np.zeros(4, np.float32), 0.1, 0.01)
    np.testing.assert_allclose(w32.asnumpy(), wr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m2.asnumpy(), mr, rtol=1e-5)


def test_sldwin_attention_matches_banded_reference():
    """Sliding-window attention ops vs a dense numpy banded reference
    (score gather, mask, context contraction; symmetric and causal-left
    windows, dilation > 1)."""
    rng = np.random.RandomState(0)
    B, L, H, D, w = 1, 10, 2, 4, 2
    q = rng.randn(B, L, H, D).astype(np.float32)
    k = rng.randn(B, L, H, D).astype(np.float32)
    v = rng.randn(B, L, H, D).astype(np.float32)
    for symmetric, dil in ((True, 1), (False, 1), (True, 2),
                           (False, 2)):
        dilation = np.full(H, dil, np.float32)
        offs = list(range(-w, (w if symmetric else 0) + 1))
        J = len(offs)
        score = invoke("_contrib_sldwin_atten_score", nd.array(q),
                       nd.array(k), nd.array(dilation), w=w,
                       symmetric=symmetric).asnumpy()
        assert score.shape == (B, L, H, J)
        ref = np.zeros((B, L, H, J), np.float32)
        for i in range(L):
            for jj, o in enumerate(offs):
                t = i + o * dil
                if 0 <= t < L:
                    for h in range(H):
                        ref[0, i, h, jj] = q[0, i, h] @ k[0, t, h]
        np.testing.assert_allclose(score, ref, rtol=1e-5, atol=1e-5)

        mask = invoke("_contrib_sldwin_atten_mask_like", nd.array(score),
                      nd.array(dilation), nd.array([float(L)]), w=w,
                      symmetric=symmetric).asnumpy()
        valid = np.zeros((B, L, H, J), np.float32)
        for i in range(L):
            for jj, o in enumerate(offs):
                t = i + o * dil
                valid[0, i, :, jj] = 1.0 if 0 <= t < L else 0.0
        np.testing.assert_array_equal(mask, valid)

        ctxo = invoke("_contrib_sldwin_atten_context", nd.array(score),
                      nd.array(v), nd.array(dilation), w=w,
                      symmetric=symmetric).asnumpy()
        refc = np.zeros((B, L, H, D), np.float32)
        for i in range(L):
            for jj, o in enumerate(offs):
                t = i + o * dil
                if 0 <= t < L:
                    for h in range(H):
                        refc[0, i, h] += ref[0, i, h, jj] * v[0, t, h]
        np.testing.assert_allclose(ctxo, refc, rtol=1e-4, atol=1e-4)


def test_psroi_pooling_reference():
    """PSROIPooling vs a direct numpy computation on a tiny grid."""
    data = np.arange(1 * 4 * 4 * 4, dtype=np.float32).reshape(1, 4, 4, 4)
    rois = np.array([[0, 0, 0, 3, 3]], np.float32)
    out = invoke("_contrib_PSROIPooling", nd.array(data), nd.array(rois),
                 spatial_scale=1.0, output_dim=1, pooled_size=2,
                 group_size=2).asnumpy()
    assert out.shape == (1, 1, 2, 2)
    # bin (ph, pw) averages channel ph*2+pw over its spatial window
    # roi [0,3]x[0,3] -> bins cover rows/cols [0,1.5) and [1.5,3)
    def avg(c, ys, ye, xs, xe):
        mask = np.zeros((4, 4), np.float32)
        for yy in range(4):
            for xx in range(4):
                if yy + 1 > ys and yy < ye and xx + 1 > xs and xx < xe:
                    mask[yy, xx] = 1
        return (data[0, c] * mask).sum() / max(mask.sum(), 1)
    expect = np.array([[avg(0, 0, 1.5, 0, 1.5), avg(1, 0, 1.5, 1.5, 3)],
                       [avg(2, 1.5, 3, 0, 1.5), avg(3, 1.5, 3, 1.5, 3)]],
                      np.float32)
    np.testing.assert_allclose(out[0, 0], expect, rtol=1e-5)


def test_box_encode_decode_roundtrip():
    """box_encode targets decoded against the same anchors must recover
    the matched ground-truth boxes (the SSD/R-CNN regression contract)."""
    anchors = np.array([[[0.1, 0.1, 0.4, 0.5], [0.5, 0.4, 0.9, 0.8]]],
                       np.float32)
    refs = np.array([[[0.15, 0.12, 0.45, 0.55], [0.48, 0.42, 0.88, 0.82]]],
                    np.float32)
    samples = np.ones((1, 2), np.float32)
    matches = np.array([[0, 1]], np.float32)
    targets, masks = invoke("_contrib_box_encode", nd.array(samples),
                            nd.array(matches), nd.array(anchors),
                            nd.array(refs))
    assert masks.asnumpy().min() == 1.0     # both rois positive
    # decode with matching stds recovers the refs
    decoded = invoke("_contrib_box_decode",
                     targets * nd.array(np.array([0.1, 0.1, 0.2, 0.2],
                                                 np.float32)),
                     nd.array(anchors), std0=1.0, std1=1.0, std2=1.0,
                     std3=1.0).asnumpy()
    np.testing.assert_allclose(decoded, refs, rtol=1e-4, atol=1e-5)


def test_deformable_conv_zero_offsets_equals_convolution():
    """With all offsets zero (and all-ones modulation), deformable conv
    must equal standard Convolution — the exactness anchor for the
    bilinear-sampling path."""
    rng = np.random.RandomState(0)
    x = rng.randn(1, 2, 6, 6).astype(np.float32)
    w = rng.randn(3, 2, 3, 3).astype(np.float32)
    want = invoke("Convolution", nd.array(x), nd.array(w), None,
                  kernel=(3, 3), pad=(1, 1), num_filter=3,
                  no_bias=True).asnumpy()
    got = invoke("_contrib_DeformableConvolution", nd.array(x),
                 nd.array(np.zeros((1, 18, 6, 6), np.float32)),
                 nd.array(w), kernel=(3, 3), pad=(1, 1), num_filter=3,
                 no_bias=True).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    got_v2 = invoke("_contrib_ModulatedDeformableConvolution", nd.array(x),
                    nd.array(np.zeros((1, 18, 6, 6), np.float32)),
                    nd.array(np.ones((1, 9, 6, 6), np.float32)),
                    nd.array(w), kernel=(3, 3), pad=(1, 1), num_filter=3,
                    no_bias=True).asnumpy()
    np.testing.assert_allclose(got_v2, want, rtol=1e-4, atol=1e-5)
    # half-modulation scales the output linearly
    got_half = invoke("_contrib_ModulatedDeformableConvolution",
                      nd.array(x),
                      nd.array(np.zeros((1, 18, 6, 6), np.float32)),
                      nd.array(np.full((1, 9, 6, 6), 0.5, np.float32)),
                      nd.array(w), kernel=(3, 3), pad=(1, 1), num_filter=3,
                      no_bias=True).asnumpy()
    np.testing.assert_allclose(got_half, 0.5 * want, rtol=1e-4, atol=1e-5)


def test_hawkesll_matches_slow_reference():
    """Hawkes log-likelihood vs a direct O(T²)-style numpy evaluation of
    intensity terms and the exponential-kernel compensator."""
    rng = np.random.RandomState(0)
    B, T, K = 1, 5, 2
    lda = np.full((B, K), 0.5, np.float32)
    alpha = np.array([0.2, 0.3], np.float32)
    beta = np.array([1.0, 2.0], np.float32)
    state = np.zeros((B, K), np.float32)
    lags = rng.rand(B, T).astype(np.float32)
    marks = rng.randint(0, K, (B, T)).astype(np.float32)
    valid = np.array([T], np.float32)
    tmax = np.array([float(lags.sum() + 1.0)], np.float32)
    ll, _ = invoke("_contrib_hawkesll", nd.array(lda), nd.array(alpha),
                   nd.array(beta), nd.array(state), nd.array(lags),
                   nd.array(marks), nd.array(valid), nd.array(tmax))
    # slow reference
    times = np.cumsum(lags[0])
    ll_ref = 0.0
    for i in range(T):
        k = int(marks[0, i])
        exc = 0.0
        for j in range(i):
            if int(marks[0, j]) == k:
                exc += np.exp(-beta[k] * (times[i] - times[j]))
        lam = lda[0, k] + alpha[k] * beta[k] * exc
        ll_ref += np.log(lam)
    comp = lda[0].sum() * tmax[0]
    for i in range(T):
        k = int(marks[0, i])
        comp += alpha[k] * (1 - np.exp(-beta[k] * (tmax[0] - times[i])))
    ll_ref -= comp
    np.testing.assert_allclose(float(ll.asnumpy()[0]), ll_ref, rtol=1e-4)


def test_npi_symbol_json_name_parity():
    """A 2.x-era symbol.json whose nodes use _npi_/_npx_ op names loads
    and executes through the registry aliases (numpy-era graph compat)."""
    import json as _json
    sym_json = _json.dumps({
        "nodes": [
            {"op": "null", "name": "data", "inputs": []},
            {"op": "null", "name": "w", "inputs": []},
            {"op": "_npx_fully_connected", "name": "fc",
             "attrs": {"num_hidden": "3", "no_bias": "True"},
             "inputs": [[0, 0, 0], [1, 0, 0]]},
            {"op": "_npx_relu", "name": "act", "inputs": [[2, 0, 0]]},
            {"op": "_npi_add", "name": "out",
             "inputs": [[3, 0, 0], [3, 0, 0]]},
        ],
        "arg_nodes": [0, 1],
        "node_row_ptr": [0, 1, 2, 3, 4, 5],
        "heads": [[4, 0, 0]],
        "attrs": {"mxnet_version": ["int", 20000]},
    })
    import mxnet_tpu as mx
    s = mx.sym.loads(sym_json)
    x = f(2, 4)
    w = f(3, 4)
    exe = s.bind(mx.cpu(), {"data": nd.array(x), "w": nd.array(w)})
    got = exe.forward()[0].asnumpy()
    want = 2 * np.maximum(x @ w.T, 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
