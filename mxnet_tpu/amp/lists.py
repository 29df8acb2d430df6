"""AMP op lists.

Reference: ``python/mxnet/contrib/amp/lists/symbol_fp16.py`` — the reference
partitions its op surface into FP16_FUNCS (always narrow), FP32_FUNCS
(always wide), FP16_FP32_FUNCS (either), WIDEST_TYPE_CASTS (match inputs),
and CONDITIONAL_FP32_FUNCS (wide for particular attribute values).

TPU-first: the narrow dtype defaults to **bfloat16** (MXU-native; same
exponent range as fp32, so dynamic loss scaling is unnecessary), and the
lists name this rebuild's canonical op names.  Anything in neither list
runs in whatever dtype its inputs already carry — XLA type-propagates the
rest of the graph, so only dtype *boundaries* need declaring.
"""

# MXU-bound ops: always cast fp32 inputs down to the target dtype — these are
# where the FLOPs are and where bf16 doubles throughput.
TARGET_DTYPE_OPS = [
    "Convolution", "Deconvolution", "FullyConnected", "RNN",
    "dot", "batch_dot", "einsum",
    "linalg_gemm", "linalg_gemm2",
    "multi_head_attention",
    "_contrib_interleaved_matmul_encdec_qk",
    "_contrib_interleaved_matmul_encdec_valatt",
    "_contrib_interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt",
]

# Numerically sensitive ops: always promote narrow float inputs to fp32
# (softmax/log/exp accumulate in ways that overflow/cancel in 8-bit-mantissa
# bf16; norms divide by small variances).
FP32_OPS = [
    "softmax", "log_softmax", "sparse_softmax_cross_entropy", "softmin",
    "masked_softmax",
    "masked_log_softmax", "softmax_cross_entropy", "SoftmaxOutput",
    "CTCLoss",
    "BatchNorm", "LayerNorm", "GroupNorm", "InstanceNorm", "RMSNorm",
    "L2Normalization", "norm", "moments", "var", "std",
    "exp", "expm1", "log", "log1p", "log2", "log10",
    "erfinv", "gammaln", "digamma", "polygamma", "gammainc", "gammaincc",
    "logsumexp", "cumsum", "cumprod", "linalg_potrf", "linalg_potri",
    "linalg_sumlogdiag", "linalg_det", "linalg_slogdet", "linalg_inverse",
    "linalg_syevd",
]

# Multi-input elementwise ops: if inputs mix float widths, cast all to the
# widest so XLA doesn't silently truncate one operand.
WIDEST_TYPE_CASTS = [
    "broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
    "broadcast_maximum", "broadcast_minimum", "broadcast_power",
    "broadcast_hypot", "broadcast_mod",
    "arctan2", "copysign", "logaddexp", "hypot", "ldexp", "nextafter",
    "where", "lerp", "concat", "stack", "heaviside",
]

# (op_name, param_name, [values]) -> run in fp32 when the attribute matches
# (reference: CONDITIONAL_FP32_FUNCS, e.g. softrelu activation).
CONDITIONAL_FP32_OPS = [
    ("Activation", "act_type", ["softrelu"]),
    ("LeakyReLU", "act_type", ["selu", "elu"]),
]
