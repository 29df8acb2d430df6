"""Deterministic demo model for serve smokes.

Both sides of a chaos run build this independently — the replicas host
it, the load driver (tools/serve_load.py) recomputes expected outputs
locally — so response *correctness* (not just arrival) is assertable
across processes.  Seeded init + pure-functional forward make the
parity exact.
"""
from __future__ import annotations

import numpy as _np

DEMO_SEED = 42
DEMO_IN = 16
DEMO_HIDDEN = 32
DEMO_OUT = 8


def demo_block():
    """The canonical demo MLP: 16 → 32(relu) → 8, Xavier(seed 42).
    HybridSequential so ``export()`` works (hot-swap tests export a
    mutated copy and SWAP replicas onto it)."""
    import mxnet_tpu as mx
    from ..gluon import nn
    mx.random.seed(DEMO_SEED)
    net = nn.HybridSequential()
    net.add(nn.Dense(DEMO_HIDDEN, in_units=DEMO_IN, activation="relu"))
    net.add(nn.Dense(DEMO_OUT, in_units=DEMO_HIDDEN))
    net.initialize(mx.init.Xavier())
    return net


def demo_example(rows: int = 1) -> list:
    """A warm/probe input batch of the demo signature."""
    return [_np.zeros((rows, DEMO_IN), _np.float32)]


def demo_requests(n: int, rows: int = 1, seed: int = 0) -> list:
    """Deterministic request stream: n single-input requests."""
    rng = _np.random.RandomState(seed)
    return [[rng.randn(rows, DEMO_IN).astype(_np.float32)]
            for _ in range(n)]


def demo_expected(x: _np.ndarray, net=None) -> _np.ndarray:
    """Reference forward through the demo block (eager, local) — what a
    correct replica must answer for ``x``.  Pass ``net`` to reuse one
    built block across many requests."""
    from ..ndarray.ndarray import NDArray
    import jax.numpy as jnp
    if net is None:
        net = demo_block()
    out = net(NDArray(jnp.asarray(x)))
    return _np.asarray(out._jax)
