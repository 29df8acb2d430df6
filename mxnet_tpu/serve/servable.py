"""Servables: versioned models behind AOT-compiled bucketed programs.

Reference: TF-Serving's loader/servable/version-manager split (arxiv
1605.08695 §3) — a *servable* is one immutable version of one model; the
*host* owns the version lifecycle (load → warm → flip → drain).  The
compilation lane reuses the repo's whole-step trace machinery
(``CompiledStep._make_forward``'s param-override trace) forward-only:
the block runs once under ``autograd.predict_mode`` per (bucket, input
signature) to build a jitted program, and every configured batch bucket
is pre-traced at deploy time (:meth:`Servable.warm`) so serve time is
pure cached-executable dispatch — the ``serve.retraces`` counter pins
"zero retraces after warmup" in bench and the dispatch-budget harness.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as _np
import jax

from ..base import MXNetError, get_env
from .. import fault as _fault
from .. import telemetry as _telemetry

__all__ = ["BucketTable", "Servable", "ModelHost", "BudgetExceeded"]


class BudgetExceeded(MXNetError):
    """Raised when admitting a servable would bust the host's HBM
    budget (``MX_SERVE_HBM_BUDGET``) — the wire layer maps this to the
    typed in-band ``(False, "budget: ...")`` refusal, so a client can
    tell "this replica is full" from a crash."""


class BucketTable:
    """The configured batch-size buckets, sorted ascending.

    ``bucket_for(n)`` returns the smallest bucket >= n (pad-to-bucket
    target), or None when n exceeds the top bucket — the admission path
    rejects those instead of compiling an unplanned shape at serve time.
    """

    def __init__(self, sizes: Sequence[int]):
        uniq = sorted({int(s) for s in sizes})
        if not uniq or uniq[0] < 1:
            raise MXNetError("BucketTable needs positive bucket sizes, "
                             "got %r" % (sizes,))
        self.sizes: Tuple[int, ...] = tuple(uniq)

    @classmethod
    def from_env(cls) -> "BucketTable":
        raw = get_env("MX_SERVE_BUCKETS") or "1,2,4,8,16"
        return cls([int(p) for p in str(raw).split(",") if p.strip()])

    @property
    def max_size(self) -> int:
        return self.sizes[-1]

    def bucket_for(self, n: int) -> Optional[int]:
        for s in self.sizes:
            if s >= n:
                return s
        return None

    def __iter__(self):
        return iter(self.sizes)

    def __repr__(self):
        return "BucketTable%r" % (self.sizes,)


def _counter(name, doc):
    return _telemetry.registry.counter(name, doc=doc)


def platform_of(arrays) -> str:
    """The platform(s) holding `arrays` — "tpu", "cpu", or a
    comma-joined list when they straddle.  A replica reports this in
    HEALTH so a client can check that the model is on the chip."""
    plats = {d.platform for a in arrays for d in a.devices()}
    return ",".join(sorted(plats)) or "none"


class Servable:
    """One immutable model version: parameters + AOT program table.

    ``block`` is any Gluon/Symbol block whose forward maps row-batched
    inputs to row-batched outputs (leading axis = batch on every input
    and output leaf) — the padding contract depends on row independence
    of the *slots*, i.e. padding rows changes nothing about real rows.

    Programs are keyed ``(bucket, input signature)`` where the signature
    is the per-input (trailing shape, dtype) tuple; :meth:`warm`
    pre-builds and pre-runs every bucket for one signature so the jit
    cache, the XLA executable AND the first-dispatch autotuning are all
    paid before the version goes live.
    """

    def __init__(self, block, name: str = "model", version: int = 1,
                 buckets: Optional[BucketTable] = None):
        from ..gluon.block import functionalize
        self.block = block
        self.name = str(name)
        self.version = int(version)
        self.buckets = buckets or BucketTable.from_env()
        self._pure, self._param_values = functionalize(block)
        #: the Context the parameters were built/loaded on (a SWAP
        #: loads its successor onto the same one); None = no parameters
        self.ctx = next((p.list_ctx()[0] for p in
                         block.collect_params().values()), None)
        # buffer-census attribution (ISSUE 10): this version's parameter
        # arrays show up under the "serve" owner bucket
        from .. import programs as _programs
        _programs.track_buffers(
            "serve", self,
            lambda sv: list(sv._param_values.values()))
        self._lock = threading.Lock()
        self._programs: Dict[Tuple, object] = {}
        self._warm_sig: Optional[Tuple] = None
        self.retraces = 0            # program builds (trace+compile)
        self.bucket_hits = 0         # dispatches served from the table
        self._c_retrace = _counter(
            "serve.retraces", "serve-side program builds (should be 0 "
            "after warmup; warm() pays them at deploy)")
        self._c_hits = _counter(
            "serve.bucket_hits", "dispatches answered by a pre-built "
            "bucket program")
        self._c_batches = _counter(
            "serve.batches", "micro-batch dispatches")
        # per-model twins (ISSUE 20): the aggregate series above stay
        # for every existing gate; the labeled ones give the fleet
        # plane a per-model breakdown on a multi-model replica
        self._c_batches_m = _telemetry.registry.counter(
            "serve.batches", doc="micro-batch dispatches",
            labels={"model": self.name})
        # in-flight dispatch tracking for the host's drain
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._closed = False

    # -- loaders ------------------------------------------------------------
    @staticmethod
    def from_block(block, params_file: Optional[str] = None, ctx=None,
                   **kwargs) -> "Servable":
        """Host a live Gluon block, optionally restoring a
        ``save_parameters`` checkpoint first."""
        if params_file:
            block.load_parameters(params_file, ctx=ctx)
        return Servable(block, **kwargs)

    @staticmethod
    def from_checkpoint(prefix: str, epoch: int = 0,
                        input_names: Sequence[str] = ("data",),
                        ctx=None, **kwargs) -> "Servable":
        """Host an exported/foreign ``<prefix>-symbol.json`` +
        ``<prefix>-%04d.params`` artifact through the existing
        ``SymbolBlock.imports`` lane (the deploy format every MXNet-era
        tool emits).  Parameters load onto ``ctx`` (default: the
        current context) as inference-only operands of the bucket
        programs."""
        from ..gluon.block import SymbolBlock
        sym_file = "%s-symbol.json" % prefix
        params_file = "%s-%04d.params" % (prefix, int(epoch))
        if not os.path.exists(params_file):
            params_file = None
        block = SymbolBlock.imports(sym_file, list(input_names),
                                    params_file, ctx=ctx)
        # serving never differentiates: no gradient buffers on the device
        block.collect_params().setattr("grad_req", "null")
        kwargs.setdefault("name", os.path.basename(prefix))
        return Servable(block, **kwargs)

    # -- program table ------------------------------------------------------
    @staticmethod
    def signature_of(arrays: Sequence) -> Tuple:
        """Per-input (trailing shape, dtype) — the part of the aval the
        bucket does not normalize.  Inputs must be ndarray-like (shape/
        dtype attributes): the admission path hands the batcher numpy
        arrays by contract, and shape reads never sync a device."""
        return tuple((tuple(int(s) for s in a.shape[1:]), str(a.dtype))
                     for a in arrays)

    def _build(self, key):
        """One jit program per (bucket, signature) key.  Kept explicit —
        rather than one jax.jit whose aval cache we cannot see — so
        retrace/hit accounting is exact and 'no serve-time retraces' is
        a checkable number, not a hope.  Routed through the program
        census (ISSUE 10) as ``serve.<model>.b<bucket>`` so every bucket
        program's compile time and memory footprint are registry
        outputs."""
        pure = self._pure

        def run_infer(param_values, xs):
            outs = pure(param_values, *xs, training=False)
            leaves = jax.tree_util.tree_leaves(outs)
            return tuple(leaves)

        from .. import programs as _programs
        return _programs.register_program(
            "serve.%s.b%d" % (self.name, int(key[0])), run_infer)

    def program(self, bucket: int, sig: Tuple):
        key = (int(bucket), sig)
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self.bucket_hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        with _telemetry.phase("retrace"):
            prog = self._build(key)
        with self._lock:
            # two racing builders: first one in wins, identical programs
            prog = self._programs.setdefault(key, prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    def warm(self, example: Sequence, outputs_expected: bool = True):
        """Pre-trace + pre-run EVERY bucket for `example`'s signature
        (`example` = per-input arrays; leading batch dim arbitrary).
        Returns self so ``deploy(Servable(...).warm(x))`` chains.  A
        respawned replica finds each bucket's XLA compile in jax's
        persistent cache (compile_cache.py)."""
        example = [_np.asarray(a) for a in example]
        sig = self.signature_of(example)
        for bucket in self.buckets:
            zeros = [_np.zeros((bucket,) + trail, dtype=dt)
                     for trail, dt in sig]
            outs = self.dispatch(bucket, zeros, warming=True)
            if outputs_expected:
                for o in outs:
                    jax.block_until_ready(o)
        with self._lock:
            self._warm_sig = sig
        return self

    @property
    def warmed_signature(self) -> Optional[Tuple]:
        with self._lock:
            return self._warm_sig

    # -- dispatch -----------------------------------------------------------
    def dispatch(self, bucket: int, padded_inputs: Sequence,
                 warming: bool = False) -> Tuple:
        """Run the bucket program over already-padded inputs; returns the
        output leaves as jax arrays (async — callers sync when they
        scatter).  One device-program launch, counted."""
        from ..engine import engine as _engine
        prog = self.program(bucket, self.signature_of(padded_inputs))
        outs = prog(self._param_values, tuple(padded_inputs))
        _engine.count_dispatch(1)
        if not warming:
            self._c_batches.inc()
            self._c_batches_m.inc()
        return outs

    # -- footprint (the HBM bin-packer's measurement; ISSUE 20) -------------
    def program_prefix(self) -> str:
        """The program-registry name prefix this servable's programs
        register under (``memory_analysis`` bytes aggregate by it)."""
        return "serve.%s." % self.name

    def live_bytes(self) -> int:
        """Bytes of device arrays this servable holds LIVE (the same
        arrays its ``buffer_census()`` owner tags claim)."""
        return sum(int(getattr(a, "nbytes", 0))
                   for a in self._param_values.values())

    def param_platform(self) -> str:
        """Where this version's parameters live, for HEALTH."""
        return platform_of(self._param_values.values())

    def footprint_bytes(self) -> int:
        """Measured HBM footprint for budget admission: live bytes
        (params + any device state) plus the peak transient bytes any
        of its registered programs needs at dispatch.  Meaningful after
        :meth:`warm` — warming is what populates ``memory_analysis``
        in the program registry, which is why the packer admits AFTER
        the warm."""
        from .. import programs as _programs
        mem = _programs.program_memory_bytes(self.program_prefix())
        return self.live_bytes() + int(mem["temp_bytes_peak"])

    # -- lifecycle ----------------------------------------------------------
    def begin(self) -> bool:
        """Claim one in-flight dispatch slot; False once retired."""
        with self._inflight_cv:
            if self._closed:
                return False
            self._inflight += 1
            return True

    def release(self) -> None:
        with self._inflight_cv:
            self._inflight = max(0, self._inflight - 1)
            self._inflight_cv.notify_all()

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait (bounded) until no dispatch is in flight, then retire:
        new begin() calls fail, the program table is dropped.  Returns
        False if in-flight work outlived the budget (retire anyway —
        outstanding jax arrays stay valid; only NEW dispatches die)."""
        deadline = _fault.Deadline(timeout)
        ok = True
        with self._inflight_cv:
            while self._inflight > 0:
                remaining = deadline.remaining()
                if remaining <= 0:
                    ok = False
                    break
                self._inflight_cv.wait(timeout=min(0.05, remaining))
            self._closed = True
        with self._lock:
            self._programs.clear()
        return ok


class ModelHost:
    """Versioned servable lifecycle, MULTI-MODEL (ISSUE 20): one host
    co-hosts N named models, each with its own version chain (load
    v(N+1) → warm → atomic flip → drain v(N)), under one HBM budget.

    ``active(model)`` is what a batcher dereferences per batch — one
    lock acquisition, never blocked by a deploy in progress (warming
    happens entirely BEFORE the flip, draining entirely after), so
    hot-swap under load serves every request from exactly one complete
    version per model.  ``active()`` with no argument keeps the
    single-model API: the DEFAULT model (first deployed).

    **Census-driven bin-packing.**  With ``MX_SERVE_HBM_BUDGET`` > 0
    (bytes), :meth:`deploy` measures the candidate's footprint AFTER
    its warm — live param/state bytes (the arrays its
    ``buffer_census()`` owner tags claim) plus the peak
    ``memory_analysis`` temp bytes of its registered programs — and
    refuses admission with :class:`BudgetExceeded` when hosted + new
    would bust the budget (a same-name redeploy gets its
    predecessor's bytes back first).  The refusal is typed so the wire
    layer can answer in-band instead of dying.
    """

    def __init__(self, hbm_budget: Optional[int] = None):
        self._lock = threading.Lock()
        self._servables: Dict[str, Servable] = {}
        self._default: Optional[str] = None
        self._history: List[Tuple[int, str]] = []
        self.hbm_budget = int(
            hbm_budget if hbm_budget is not None else
            get_env("MX_SERVE_HBM_BUDGET", 0, int))
        #: per-model engines (micro-batchers), managed by the serving
        #: layer; lives on the host so the wire layer's model routing
        #: stays a read off the one object that owns model lifecycle
        self.engines: Dict[str, object] = {}

    def models(self) -> List[str]:
        with self._lock:
            return sorted(self._servables)

    def active(self, model: Optional[str] = None) -> Servable:
        with self._lock:
            name = model if model is not None else self._default
            sv = self._servables.get(name) if name is not None else None
        if sv is None:
            if model is None:
                raise MXNetError("ModelHost: no servable deployed")
            raise MXNetError(
                "ModelHost: unknown model %r (hosted: %s)"
                % (model, ", ".join(sorted(self._servables)) or "none"))
        return sv

    @property
    def default_model(self) -> Optional[str]:
        with self._lock:
            return self._default

    @property
    def version(self) -> int:
        """The DEFAULT model's live version (single-model API)."""
        with self._lock:
            name = self._default
            sv = self._servables.get(name) if name is not None else None
            return sv.version if sv is not None else 0

    def version_of(self, model: str) -> int:
        with self._lock:
            sv = self._servables.get(model)
            return sv.version if sv is not None else 0

    def _engine_servables(self) -> Dict[str, object]:
        """Decode-engine servables co-hosted on this replica (target +
        draft of a speculative pair ride ``engines``), excluding names
        already counted as deployed servables — these share the HBM
        budget with the predict-lane models."""
        with self._lock:
            hosted = set(self._servables)
            engines = list(self.engines.values())
        out: Dict[str, object] = {}
        for eng in engines:
            for sv in (getattr(eng, "servable", None),
                       getattr(eng, "draft", None)):
                if sv is None or not hasattr(sv, "footprint_bytes"):
                    continue
                if sv.name in hosted or sv.name in out:
                    continue
                out[sv.name] = sv
        return out

    def used_bytes(self) -> int:
        """Measured footprint of every hosted servable (recomputed —
        the census reads live handles, so this tracks reality, not an
        admission-time estimate), plus any co-hosted decode engines'
        models (a speculative draft/target pair shares the budget)."""
        with self._lock:
            svs = list(self._servables.values())
        svs.extend(self._engine_servables().values())
        return sum(sv.footprint_bytes() for sv in svs)

    def packing_report(self) -> Dict[str, object]:
        """The bin-packer's health/FLEET surface: per-model measured
        footprints against the budget."""
        with self._lock:
            svs = dict(self._servables)
            default = self._default
        per_model = {name: {"version": sv.version,
                            "footprint_bytes": sv.footprint_bytes()}
                     for name, sv in svs.items()}
        for name, sv in self._engine_servables().items():
            per_model[name] = {"version": sv.version,
                               "footprint_bytes": sv.footprint_bytes(),
                               "engine": getattr(sv, "engine", "decode")}
        used = sum(m["footprint_bytes"] for m in per_model.values())
        return {
            "hbm_budget_bytes": self.hbm_budget,
            "used_bytes": used,
            "free_bytes": (self.hbm_budget - used
                           if self.hbm_budget > 0 else None),
            "default_model": default,
            "models": per_model,
        }

    def deploy(self, servable: Servable, example: Optional[Sequence] = None,
               drain_timeout: float = 30.0) -> Servable:
        """Warm `servable` (when an example is given and it is not
        already warm), admit it against the HBM budget, flip it live
        under its name, drain the same-name predecessor.  Raises
        :class:`BudgetExceeded` (servable NOT retained) on a budget
        bust."""
        if example is not None and servable.warmed_signature is None:
            servable.warm(example)
        name = servable.name
        with self._lock:
            prev = self._servables.get(name)
            if prev is not None and servable.version <= prev.version:
                raise MXNetError(
                    "ModelHost: version %d is not newer than the active "
                    "%d" % (servable.version, prev.version))
        if self.hbm_budget > 0:
            # admission AFTER warm: the footprint is measured, not
            # estimated — warm populated memory_analysis and the params
            # /state are resident
            new_bytes = servable.footprint_bytes()
            with self._lock:
                others = [sv for n, sv in self._servables.items()
                          if n != name]
            others.extend(sv for n, sv in
                          self._engine_servables().items() if n != name)
            used = sum(sv.footprint_bytes() for sv in others)
            if used + new_bytes > self.hbm_budget:
                raise BudgetExceeded(
                    "ModelHost: admitting %r v%d (%d bytes) would use "
                    "%d of %d budget bytes (MX_SERVE_HBM_BUDGET; %d "
                    "hosted: %s)"
                    % (name, servable.version, new_bytes,
                       used + new_bytes, self.hbm_budget, len(others),
                       ", ".join(sorted(sv.name for sv in others))
                       or "none"))
        with self._lock:
            prev = self._servables.get(name)
            if prev is not None and servable.version <= prev.version:
                raise MXNetError(
                    "ModelHost: version %d is not newer than the active "
                    "%d" % (servable.version, prev.version))
            old = prev
            self._servables[name] = servable
            if self._default is None:
                self._default = name
            self._history.append((servable.version, name))
        if old is not None:
            old.drain(timeout=drain_timeout)
        return servable

    def history(self) -> List[Tuple[int, str]]:
        with self._lock:
            return list(self._history)


# ---------------------------------------------------------------------------
# Program contracts (ISSUE 11): the serve bucket table's declared
# trace-closure proof.  One contract covers every `serve.demo.b<N>`
# bucket program of the canonical demo servable under the CONFIGURED
# bucket table (MX_SERVE_BUCKETS): the verifier lowers each bucket
# program device-free and proves the admission path is CLOSED — every
# admissible batch size pads to a bucket whose signature is in the
# compiled set, and over-bucket sizes are rejected before the jit — so
# "zero serve-time retraces" is a static theorem, not a bench
# observation.  Builders run only inside the contracts verifier.
# ---------------------------------------------------------------------------

import functools as _functools


@_functools.lru_cache(maxsize=1)
def _demo_contract_built():
    from ..programs import ContractCase, ContractClosure
    from .demo import demo_block, DEMO_IN
    table = BucketTable.from_env()
    sv = Servable(demo_block(), name="demo", version=1, buckets=table)
    params_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in sv._param_values.items()}
    sig = (((DEMO_IN,), "float32"),)

    def args_for(bucket):
        return (params_abs,
                (jax.ShapeDtypeStruct((bucket, DEMO_IN), _np.float32),))

    cases = [ContractCase("serve.demo.b%d" % b, args_for(b),
                          label="b%d" % b, target=sv.program(b, sig))
             for b in table]

    def resolve(rows):
        # mirror the runtime admission/padding path exactly: the
        # batcher pads a rows-row batch up to bucket_for(rows), and
        # over-bucket batches are refused at admission (never reach a
        # jit) — resolving to None
        bucket = table.bucket_for(int(rows))
        return None if bucket is None else args_for(bucket)

    closure = ContractClosure(range(1, table.max_size + 3), resolve)
    return cases, closure


def _declare_serve_contracts():
    from ..programs import declare_contract
    declare_contract(
        "serve.demo", lambda: _demo_contract_built()[0],
        donate_argnums=(),
        temp_budget_bytes=1 << 20,
        closure=lambda: _demo_contract_built()[1],
        description="demo servable's AOT bucket table: no donations "
                    "(params are shared across dispatches), trace "
                    "signatures closed over the MX_SERVE_BUCKETS "
                    "admission set")


_declare_serve_contracts()
