"""The repo-specific rule set.

Each rule enforces an invariant a prior PR bought (see the
"Enforced invariants" table in docs/ARCHITECTURE.md).  All analysis is
file-local: call graphs do not cross imports, so a sync hidden behind an
imported helper needs a root entry for that helper's own file.  That is a
deliberate trade — file-local analysis is fast, dependency-free and has
no false positives from dynamic dispatch — and the hot-path root table
below covers both sides of every cross-file hot edge (Trainer._update ->
Updater.__call__, Module.update_metric -> metric.update, ...).
"""
from __future__ import annotations

import ast
import fnmatch
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .core import (Diagnostic, FileContext, Rule, register_rule,
                   _attr_chain)

# ---------------------------------------------------------------------------
# host-sync-in-hot-path
# ---------------------------------------------------------------------------

# (file pattern, [qualname patterns]) — the training-step hot path as rooted
# per file.  Cross-file hot edges are covered by rooting the callee's own
# entry points (file-local analysis never follows imports).
HOT_PATH_ROOTS: List[Tuple[str, List[str]]] = [
    ("mxnet_tpu/gluon/trainer.py",
     ["Trainer.step", "Trainer.update", "Trainer._update",
      "Trainer.allreduce_grads", "Trainer._allreduce_grads"]),
    # the whole-step compiled lane (ISSUE 7): every host-side function on
    # the per-dispatch path is a hot root — one sync here stalls the
    # single-program pipeline exactly like a per-op sync used to.  The
    # traced bodies (_traced_step_window / _traced_fit_step and their
    # closures) are additionally jit-purity targets via their
    # jax.jit(...) sites.
    ("mxnet_tpu/step.py",
     ["CompiledStep.step", "CompiledStep.run_window", "CompiledStep._run",
      "CompiledStep._plan", "CompiledStep._lr_rows",
      "CompiledStep._gather_state", "CompiledStep._write_back"]),
    ("mxnet_tpu/module/*.py", ["*.update", "*.update_metric"]),
    ("mxnet_tpu/model.py", ["*.update", "*.update_metric"]),
    ("mxnet_tpu/metric.py", ["*.update", "*.update_dict"]),
    ("mxnet_tpu/monitor.py", ["Monitor.tic", "Monitor.toc"]),
    ("mxnet_tpu/optimizer/*.py",
     ["Updater.__call__", "*.fused_update", "*._fused_apply", "*.update",
      "*.update_multi_precision"]),
    # telemetry span/record helpers (ISSUE 8) run inside every step
    # phase — Trainer.step, the fit loops, CompiledStep dispatches all
    # cross into this file per batch, so a host sync here stalls the
    # pipeline exactly like one in the trainer would.  Spans are
    # dispatch-time by contract; this root machine-checks it (the
    # tests/test_telemetry.py reinjection test trips this entry).
    ("mxnet_tpu/telemetry.py",
     ["phase", "note_step", "heartbeat_payload", "rpc_span",
      "Span.*", "_PhaseSpan.*", "FlightRecorder.record",
      "Counter.*", "Gauge.*", "Histogram.*"]),
    # the serving batcher's dispatch loop (ISSUE 9): a host sync between
    # dequeue and dispatch serializes the whole fleet's latency — the
    # scatter-side device→host read belongs on the handler threads
    # (_Pending.result/_Batch.host), never in the loop.  The
    # tests/test_mxlint.py reinjection test proves a blocking host read
    # reintroduced into the loop trips this entry.
    ("mxnet_tpu/serve/batcher.py",
     ["Batcher._loop", "Batcher._collect", "Batcher._dispatch",
      "Batcher.submit"]),
    # the servable dispatch path is the other side of the batcher's hot
    # edge (file-local analysis never follows imports)
    ("mxnet_tpu/serve/servable.py",
     ["Servable.dispatch", "Servable.program", "Servable.signature_of",
      "ModelHost.active"]),
    # the decode pump + slot allocator (ISSUE 15): ONE host sync
    # between decode dispatches serializes every active generation's
    # token cadence — sampled tokens stay device-resident between
    # steps, and the device→host read belongs ONLY to the harvester
    # thread (_harvest_once, deliberately NOT rooted).  The
    # tests/test_mxlint.py reinjection test proves a blocking host
    # read between state dequeue and dispatch trips this entry.
    ("mxnet_tpu/serve/decode.py",
     ["DecodeBatcher._loop", "DecodeBatcher._tick",
      "DecodeBatcher._retire", "DecodeBatcher._admit",
      "DecodeBatcher._active", "DecodeBatcher._step",
      "DecodeBatcher._dispatch_prefill", "DecodeBatcher._hq_put",
      "DecodeBatcher.submit", "DecodeServable.dispatch_step",
      "DecodeServable.dispatch_prefill", "DecodeServable.step_program",
      "DecodeServable.prefill_program",
      # the paged engine (ISSUE 18): admission planning (hash lookups,
      # page allocation, chunk layout) and the chunk scheduler run
      # between dequeue and dispatch every tick — pure host
      # bookkeeping by contract, same no-sync rule
      "PagedDecodeBatcher._tick", "PagedDecodeBatcher._retire",
      "PagedDecodeBatcher._admit", "PagedDecodeBatcher._plan",
      "PagedDecodeBatcher._active",
      "PagedDecodeBatcher._next_chunk_slot",
      "PagedDecodeBatcher._dispatch_chunk_for",
      "PagedDecodeBatcher._step",
      "PagedDecodeServable.dispatch_step",
      "PagedDecodeServable.dispatch_chunk",
      "PagedDecodeServable.step_program",
      "PagedDecodeServable.chunk_program"]),
    # the paged KV allocator + prefix hash table (ISSUE 18) sit inside
    # the pump's admission path — every method is per-tick bookkeeping
    # (free lists, refcounts, rolling hashes over host ints) and must
    # never touch the device or block.  The tests/test_mxlint.py
    # reinjection test proves a host sync smuggled into alloc() trips
    # this entry.
    ("mxnet_tpu/serve/paging.py",
     ["PageAllocator.alloc", "PageAllocator.lookup",
      "PageAllocator.publish", "PageAllocator.release",
      "PageAllocator.free_pages", "PageAllocator.shared_extra_refs",
      "chain_hash", "page_hashes"]),
    # the program census (ISSUE 10) wraps EVERY jit dispatch: its call
    # path and record helpers are dispatch-time bookkeeping by contract
    # (shape/aval reads only — never a device sync), and the buffer
    # census walks live-array HANDLES (nbytes metadata, no transfer).
    # The tests/test_mxlint.py reinjection test trips this entry.
    ("mxnet_tpu/programs.py",
     ["Program.__call__", "Program._compile", "ProgramRecord.note_compile",
      "signature_of", "diff_signatures", "buffer_census",
      "LeakDetector.check"]),
    # the async input pipeline's consumer handoff (ISSUE 13): __next__
    # runs once per training step between batches — a device sync or
    # host pull here re-serializes exactly the overlap the prefetcher
    # exists to create (the device_put lives on the producer thread by
    # design).  The tests/test_prefetch.py reinjection test trips
    # this entry.
    ("mxnet_tpu/io/prefetch.py",
     ["DevicePrefetcher.__next__", "DevicePrefetcher._put"]),
    # the fleet collector's scrape/merge loop (ISSUE 12) runs forever
    # NEXT TO the training/serving processes it observes — a host sync
    # (or any device pull) reintroduced here would periodically stall
    # the very fleet it measures.  The merge algebra is dict arithmetic
    # by contract (no numpy, no jax); this root machine-checks it (the
    # tests/test_fleet.py reinjection test trips this entry).
    ("mxnet_tpu/fleet.py",
     ["FleetCollector.scrape_once", "FleetCollector._scrape_member",
      "FleetCollector._scrape_heartbeat", "FleetCollector._fold",
      "FleetCollector._publish", "FleetCollector._rebase_counters",
      "FleetCollector._hist_delta", "merge_snapshots",
      "merge_bucket_maps", "quantile_from_buckets",
      "StragglerDetector.update", "SLOTracker.update"]),
]

_SYNC_ATTRS = {"asnumpy", "asscalar", "item", "wait_to_read", "tolist"}
_NUMPY_PULLS = ("numpy.asarray", "numpy.array", "numpy.frombuffer")


def _is_numpy_pull(ctx: FileContext, func: ast.AST) -> bool:
    return any(ctx.resolves_to(func, d) for d in _NUMPY_PULLS)


def _program_fn_arg(ctx: FileContext, call: ast.AST):
    """The traced-fn argument of a program-census jit site (ISSUE 10):
    ``register_program(name, fn, **jit_kw)`` is the repo's drop-in for
    ``jax.jit(fn, **jit_kw)`` — its second positional arg is the traced
    body, and the same jit kwargs (static_argnums, donate_argnums) apply.
    Returns the fn node, or None when `call` is not such a site."""
    if not isinstance(call, ast.Call) or len(call.args) < 2:
        return None
    f = call.func
    if ctx.resolves_to(f, "mxnet_tpu.programs.register_program") or \
            (isinstance(f, ast.Name) and f.id == "register_program") or \
            (isinstance(f, ast.Attribute) and f.attr == "register_program"):
        return call.args[1]
    return None


@register_rule
class HostSyncInHotPath(Rule):
    id = "host-sync-in-hot-path"
    description = ("device->host syncs (.asnumpy()/.item()/np.asarray/"
                   "waitall) inside functions reachable from the training "
                   "step; each one stalls the XLA pipeline and breaks the "
                   "O(1)-dispatches-per-step budget")
    invariant_from = "ISSUE 3 (single-dispatch training step)"
    path_patterns = tuple(sorted({pat for pat, _ in HOT_PATH_ROOTS}))

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        roots: List[str] = []
        for pat, quals in HOT_PATH_ROOTS:
            if not fnmatch.fnmatch(ctx.path, pat):
                continue
            for qual in ctx.functions:
                if any(fnmatch.fnmatch(qual, qp) for qp in quals):
                    roots.append(qual)
        if not roots:
            return
        # BFS with provenance so the message names the reaching root
        via: Dict[str, str] = {}
        stack = [(r, r) for r in roots]
        while stack:
            qual, root = stack.pop()
            if qual in via:
                continue
            via[qual] = root
            for callee in ctx.call_graph.get(qual, ()):
                stack.append((callee, root))
        for qual, root in sorted(via.items()):
            fn = ctx.functions[qual]
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                what = None
                if isinstance(f, ast.Attribute) and f.attr in _SYNC_ATTRS:
                    what = ".%s()" % f.attr
                elif isinstance(f, ast.Attribute) and f.attr == "waitall":
                    what = "waitall()"
                elif isinstance(f, ast.Name) and f.id == "waitall":
                    what = "waitall()"
                elif _is_numpy_pull(ctx, f):
                    what = "np.%s()" % f.attr if isinstance(f, ast.Attribute)\
                        else "np.asarray()"
                elif isinstance(f, ast.Name) and f.id == "open":
                    # ISSUE 13: the persistent compile cache made disk
                    # I/O a runtime concern — it lives in
                    # Program._compile (cold path) by contract; a file
                    # open reintroduced on a per-dispatch path (the
                    # batcher loop, the prefetch handoff, the trainer
                    # step) stalls the pipeline exactly like a device
                    # sync would
                    yield ctx.diag(
                        self.id, node,
                        "open() in %s (hot path via %s): disk I/O on a "
                        "per-dispatch path; cache/file reads belong on "
                        "the cold (compile/build) path" % (qual, root))
                    continue
                if what:
                    yield ctx.diag(
                        self.id, node,
                        "%s in %s (hot path via %s) forces a device->host "
                        "sync every batch; accumulate device-side and drain "
                        "once outside the step" % (what, qual, root))


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------

_WALL_CLOCK = ("time.time", "time.monotonic", "time.perf_counter",
               "time.process_time", "time.sleep")


def _donate_positions(call: ast.Call) -> Optional[Set[int]]:
    """Literal donate_argnums positions of a jax.jit call; None if absent
    or not statically known.  An `X if flag else ()` conditional takes the
    union — the use-after bug only bites when donation is on."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        vals = [kw.value]
        if isinstance(kw.value, ast.IfExp):
            vals = [kw.value.body, kw.value.orelse]
        out: Set[int] = set()
        for v in vals:
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, int):
                        out.add(el.value)
        return out or None
    return None


def _static_param_names(fn: ast.AST,
                        jit_call: Optional[ast.Call]) -> Set[str]:
    """Parameters a tracer never flows through: static_argnums/argnames at
    the jit site, plus any parameter with a default (registry op `params`
    are static by contract)."""
    static: Set[str] = set()
    args = fn.args
    pos = [a.arg for a in getattr(args, "posonlyargs", [])] + \
          [a.arg for a in args.args]
    if jit_call is not None:
        for kw in jit_call.keywords:
            if kw.arg == "static_argnames":
                v = kw.value
                elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
                for el in elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, str):
                        static.add(el.value)
            elif kw.arg == "static_argnums":
                v = kw.value
                elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
                for el in elts:
                    if isinstance(el, ast.Constant) and \
                            isinstance(el.value, int) and \
                            el.value < len(pos):
                        static.add(pos[el.value])
    ndefaults = len(args.defaults)
    if ndefaults:
        static.update(a for a in pos[-ndefaults:])
    static.update(a.arg for a in args.kwonlyargs)
    if args.vararg:
        pass  # *arrays stay traced
    if args.kwarg:
        static.add(args.kwarg.arg)  # **params: static attrs by contract
    return static


def _is_jax_jit(ctx: FileContext, node: ast.AST) -> bool:
    return ctx.resolves_to(node, "jax.jit") or \
        ctx.resolves_to(node, "jax.experimental.pjit.pjit")


def _collect_jit_functions(ctx: FileContext):
    """(fn node -> jit call-or-None) for every function this file jits
    or registers as an op kernel — shared by jit-purity and
    retrace-hazard."""
    # every def in the file, by name (incl. nested), for by-name marks
    defs_by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)
    marked: Dict[ast.AST, Optional[ast.Call]] = {}
    in_ops = fnmatch.fnmatch(ctx.path, "mxnet_tpu/ops/*.py")

    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                jit_call = None
                hit = False
                if _is_jax_jit(ctx, dec):
                    hit = True
                elif isinstance(dec, ast.Call):
                    if _is_jax_jit(ctx, dec.func):
                        hit, jit_call = True, dec
                    elif ctx.resolves_to(dec.func, "functools.partial") \
                            and dec.args and _is_jax_jit(ctx, dec.args[0]):
                        hit, jit_call = True, dec
                    elif in_ops and ctx.resolves_to(
                            dec.func, "mxnet_tpu.ops.registry.register")\
                            or in_ops and isinstance(dec.func, ast.Name)\
                            and dec.func.id == "register":
                        # no_jit exempts only when truthy (or not a
                        # literal — then be conservative and exempt)
                        if not any(kw.arg == "no_jit" and
                                   (not isinstance(kw.value,
                                                   ast.Constant) or
                                    kw.value.value)
                                   for kw in dec.keywords):
                            hit = True
                if hit:
                    marked[node] = jit_call
        elif isinstance(node, ast.Call):
            fn_arg = None
            jit_call = None
            if _is_jax_jit(ctx, node.func) and node.args:
                fn_arg, jit_call = node.args[0], node
            elif _program_fn_arg(ctx, node) is not None:
                # register_program(name, fn, **jit_kw): fn is traced
                # exactly like jax.jit(fn, **jit_kw)'s arg (ISSUE 10)
                fn_arg, jit_call = _program_fn_arg(ctx, node), node
            elif in_ops and isinstance(node.func, ast.Name) and \
                    node.func.id == "register" and len(node.args) >= 2:
                if not any(kw.arg == "no_jit" and
                           isinstance(kw.value, ast.Constant) and
                           kw.value.value for kw in node.keywords):
                    fn_arg = node.args[1]
            if isinstance(fn_arg, ast.Name):
                for d in defs_by_name.get(fn_arg.id, ()):
                    marked.setdefault(d, jit_call)
    return marked


@register_rule
class JitPurity(Rule):
    id = "jit-purity"
    description = ("side effects (print/open/wall-clock/env reads/python "
                   "RNG/global writes/host syncs) and data-dependent "
                   "python branches inside functions that jax traces — "
                   "they run once at trace time (or crash), not per step")
    invariant_from = "seed (pure-traceable op registry contract)"

    def _jit_functions(self, ctx: FileContext):
        return _collect_jit_functions(ctx)

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        for fn, jit_call in sorted(self._jit_functions(ctx).items(),
                                   key=lambda kv: kv[0].lineno):
            static = _static_param_names(fn, jit_call)
            params = {a.arg for a in fn.args.args} | \
                {a.arg for a in getattr(fn.args, "posonlyargs", [])}
            traced = params - static
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    yield ctx.diag(self.id, node,
                                   "`global` write inside jitted %r runs at "
                                   "trace time, not per call" % fn.name)
                elif isinstance(node, ast.Call):
                    f = node.func
                    if isinstance(f, ast.Name) and f.id in ("print", "open",
                                                            "input"):
                        yield ctx.diag(
                            self.id, node,
                            "%s() inside jitted %r is a trace-time side "
                            "effect (use jax.debug.print / hoist the I/O)"
                            % (f.id, fn.name))
                    elif any(ctx.resolves_to(f, d) for d in _WALL_CLOCK):
                        yield ctx.diag(
                            self.id, node,
                            "wall-clock read inside jitted %r is baked in "
                            "at trace time" % fn.name)
                    elif ctx.resolves_to(f, "os.getenv") or \
                            (isinstance(f, ast.Attribute) and
                             f.attr in ("get_env", "getenv")) or \
                            (isinstance(f, ast.Name) and
                             f.id in ("get_env", "getenv")):
                        yield ctx.diag(
                            self.id, node,
                            "env read inside jitted %r is baked in at trace "
                            "time; pass it as a static argument" % fn.name)
                    elif isinstance(f, ast.Attribute) and \
                            f.attr in ("asnumpy", "item", "asscalar"):
                        yield ctx.diag(
                            self.id, node,
                            ".%s() inside jitted %r forces concretization "
                            "under trace" % (f.attr, fn.name))
                    else:
                        chain = _attr_chain(f)
                        if chain:
                            origin = ctx.import_aliases.get(chain[0],
                                                            chain[0])
                            full = ".".join([origin] + chain[1:])
                            if full.startswith("random.") or \
                                    full.startswith("numpy.random."):
                                yield ctx.diag(
                                    self.id, node,
                                    "python/numpy RNG inside jitted %r is "
                                    "trace-frozen; thread a jax PRNG key "
                                    "instead" % fn.name)
                elif isinstance(node, ast.Attribute) and \
                        _attr_chain(node) is not None:
                    chain = _attr_chain(node)
                    origin = ctx.import_aliases.get(chain[0], chain[0])
                    if ".".join([origin] + chain[1:]).startswith(
                            "os.environ"):
                        yield ctx.diag(
                            self.id, node,
                            "os.environ access inside jitted %r is baked in "
                            "at trace time" % fn.name)
                elif isinstance(node, (ast.If, ast.While)):
                    d = self._data_dep_branch(ctx, node, traced, fn)
                    if d:
                        yield d

    def _data_dep_branch(self, ctx, node, traced: Set[str], fn):
        """`if x > 0:` on a traced array argument — TracerBoolConversionError
        at runtime (or silently trace-frozen).  Shape/dtype attribute
        reads (`x.ndim`, `x.shape[0]`) are static and exempt, as are
        `is None` / isinstance checks."""
        # A traced name only counts when its VALUE flows into the branch
        # decision directly: bare (`if x:`), compared (`if x > 0:`), or
        # indexed (`if x[0]:`).  Excluded subtrees are static or at worst
        # loud at trace time on their own:
        #   - Attribute chains (`x.ndim`, `x.shape[0]`, `x.dtype`)
        #   - Call arguments (`isinstance(x, ...)`, `len(x)`, helper
        #     predicates over shape/dtype)
        #   - `is` / `is not` comparisons (None sentinels)
        real: List[str] = []

        def scan(sub):
            if isinstance(sub, (ast.Attribute, ast.Call)):
                return
            if isinstance(sub, ast.Compare) and \
                    all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in sub.ops):
                return
            if isinstance(sub, ast.Name) and sub.id in traced and \
                    isinstance(sub.ctx, ast.Load):
                real.append(sub.id)
            for child in ast.iter_child_nodes(sub):
                scan(child)

        scan(node.test)
        if real:
            return ctx.diag(
                self.id, node,
                "branch on traced argument%s %s inside jitted %r is "
                "data-dependent python control flow; use lax.cond/jnp.where "
                "or mark the argument static" %
                ("s" if len(real) > 1 else "", ", ".join(sorted(set(real))),
                 fn.name))
        return None


# ---------------------------------------------------------------------------
# wall-clock-in-fault-path
# ---------------------------------------------------------------------------

@register_rule
class WallClockInFaultPath(Rule):
    id = "wall-clock-in-fault-path"
    description = ("raw time.time()/monotonic()/sleep() in retry/timeout/"
                   "liveness code that must use mxnet_tpu.fault's "
                   "injectable clock, so chaos tests can fast-forward it")
    invariant_from = "ISSUE 1 (virtual-clock fault tolerance)"
    path_patterns = ("mxnet_tpu/fault.py", "mxnet_tpu/health.py",
                     "mxnet_tpu/kvstore/*.py")

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.Attribute, ast.Name)):
                continue
            if not isinstance(getattr(node, "ctx", None), ast.Load):
                continue
            # a bare module alias ("time") resolves to "time", never to
            # "time.time", so plain imports don't flag
            for dotted in _WALL_CLOCK:
                if ctx.resolves_to(node, dotted):
                    yield ctx.diag(
                        self.id, node,
                        "%s in fault-path code: use mxnet_tpu.fault."
                        "%s() so chaos tests can drive it with a "
                        "virtual clock" %
                        (dotted, "sleep" if dotted.endswith("sleep")
                         else "now"))
                    break


# ---------------------------------------------------------------------------
# env-var-registry
# ---------------------------------------------------------------------------

@register_rule
class EnvVarRegistry(Rule):
    id = "env-var-registry"
    description = ("every MX_*/MXNET_* env read must go through "
                   "mxnet_tpu.base.get_env and be declared in "
                   "base.ENV_CATALOG (docs/ENV_VARS.md regenerates from "
                   "it); ad-hoc os.environ reads dodge overrides, typed "
                   "defaults and the doc")
    invariant_from = "ISSUE 1-3 (documented MX_* env surface)"
    # NB fnmatch '*' crosses '/': this one pattern covers every depth
    path_patterns = ("mxnet_tpu/*.py",)

    _EXEMPT = ("mxnet_tpu/base.py",)  # the accessor itself

    def _is_mx(self, name: str) -> bool:
        return name.startswith("MX_") or name.startswith("MXNET_")

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        if ctx.path in self._EXEMPT:
            return
        for node in ast.walk(ctx.tree):
            name = None
            adhoc = False
            if isinstance(node, ast.Call):
                f = node.func
                chain = _attr_chain(f)
                if chain:
                    origin = ctx.import_aliases.get(chain[0], chain[0])
                    full = ".".join([origin] + chain[1:])
                    lit = (node.args and
                           isinstance(node.args[0], ast.Constant) and
                           isinstance(node.args[0].value, str) and
                           node.args[0].value)
                    if full in ("os.environ.get", "os.getenv"):
                        name, adhoc = lit, True
                    elif full.endswith("get_env") or full == "util.getenv" \
                            or (isinstance(f, ast.Name) and
                                f.id in ("get_env", "getenv")):
                        name = lit
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load):
                chain = _attr_chain(node.value)
                if chain:
                    origin = ctx.import_aliases.get(chain[0], chain[0])
                    if ".".join([origin] + chain[1:]) == "os.environ":
                        sl = node.slice
                        if isinstance(sl, ast.Constant) and \
                                isinstance(sl.value, str):
                            name, adhoc = sl.value, True
            if not name or not self._is_mx(name):
                continue
            if adhoc:
                yield ctx.diag(
                    self.id, node,
                    "ad-hoc env read of %s: route it through "
                    "mxnet_tpu.base.get_env (typed, override-aware, "
                    "catalog-documented)" % name)
            if ctx.catalog is not None and name not in ctx.catalog:
                yield ctx.diag(
                    self.id, node,
                    "%s is not declared in base.ENV_CATALOG — add it (with "
                    "default + doc line) and regenerate docs/ENV_VARS.md "
                    "via tools/gen_env_docs.py" % name)


# ---------------------------------------------------------------------------
# donation-after-use
# ---------------------------------------------------------------------------

@register_rule
class DonationAfterUse(Rule):
    id = "donation-after-use"
    description = ("an argument passed at a donate_argnums position is "
                   "invalidated by XLA buffer donation; reading it after "
                   "the call returns garbage or errors on hardware (CPU "
                   "silently skips donation, hiding the bug)")
    invariant_from = "ISSUE 3 (donated fused-optimizer buffers)"

    # The INVERSE failure mode — a donation XLA silently DROPS because
    # no output matches the donated leaf's shape+dtype, leaving both
    # generations of the buffer live on TPU — is not statically visible
    # in source and is covered by the contract lane instead:
    # `python -m tools.mxlint --contracts` lowers every contracted
    # program and emits `contract-donation-dropped` when a declared
    # donation fails to appear in the executable's input→output
    # aliasing (with jax's "donated buffers were not usable" warning
    # attached).  A donated-but-value-unused arg (jax prunes it; e.g.
    # the bf16 weights of a multi-precision Adam apply, whose new
    # values derive from the fp32 masters) is a no-op donation — the
    # verifier NOTES it in the budget table (`pruned` column) without
    # flagging.  See docs/TESTING.md §5 and ISSUE 11.

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        # 1. name -> donated positions, for `f = jax.jit(g, donate_argnums=...)`
        #    bindings (local names and self.X attributes, file-wide)
        bound: Dict[str, Set[int]] = {}
        self_bound: Dict[str, Set[int]] = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign) or \
                    not isinstance(node.value, ast.Call):
                continue
            call = node.value
            if not (ctx.resolves_to(call.func, "jax.jit") or
                    _program_fn_arg(ctx, call) is not None):
                continue
            donated = _donate_positions(call)
            if not donated:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    bound[tgt.id] = donated
                elif isinstance(tgt, ast.Attribute) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "self":
                    self_bound[tgt.attr] = donated
        # 2. scan every function for calls through those bindings (or a
        #    direct jax.jit(...)(...) call) and reads-after of donated args
        for qual, fn in sorted(ctx.functions.items()):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                donated = None
                f = node.func
                if isinstance(f, ast.Name) and f.id in bound:
                    donated = bound[f.id]
                elif isinstance(f, ast.Attribute) and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id == "self" and f.attr in self_bound:
                    donated = self_bound[f.attr]
                elif isinstance(f, ast.Call) and \
                        (ctx.resolves_to(f.func, "jax.jit") or
                         _program_fn_arg(ctx, f) is not None):
                    donated = _donate_positions(f)
                if not donated:
                    continue
                donated_names = {a.id for i, a in enumerate(node.args)
                                 if i in donated and isinstance(a, ast.Name)}
                if not donated_names:
                    continue
                yield from self._reads_after(ctx, fn, node, donated_names,
                                             qual)

    def _reads_after(self, ctx, fn, call, names: Set[str], qual: str):
        call_line = getattr(call, "end_lineno", call.lineno)
        names = set(names)
        # `a = fn(a, b)` rebinds on the call's own line: the assignment
        # targets of the statement containing the call kill the taint
        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Assign) and \
                    any(n is call for n in ast.walk(stmt.value)):
                for tgt in stmt.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            names.discard(n.id)
        if not names:
            return
        events = []   # (lineno, name, is_store)
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id in names and \
                    node.lineno > call_line:
                events.append((node.lineno, node.id,
                               isinstance(node.ctx, ast.Store), node))
        events.sort(key=lambda e: e[0])
        dead = set(names)
        for lineno, name, is_store, node in events:
            if name not in dead:
                continue
            if is_store:
                dead.discard(name)   # rebound: old buffer unreachable
            else:
                yield ctx.diag(
                    self.id, node,
                    "%r is read after being passed at a donated position "
                    "of a donate_argnums-jitted call in %s; its buffer "
                    "belongs to XLA now — rebind the result or drop "
                    "donation" % (name, qual))
                dead.discard(name)   # one report per buffer per call


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

def _literal_static_spec(call: ast.Call) -> Tuple[Set[int], Set[str]]:
    """(static positions, static names) literally declared at a jit
    call site — the single source both halves of the retrace analysis
    (bindings and direct calls) read, so a parsing fix lands once."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        v = kw.value
        elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
        for el in elts:
            if not isinstance(el, ast.Constant):
                continue
            if kw.arg == "static_argnums" and isinstance(el.value, int):
                nums.add(el.value)
            elif kw.arg == "static_argnames" and \
                    isinstance(el.value, str):
                names.add(el.value)
    return nums, names


def _jit_call_bindings(ctx: FileContext):
    """Names (locals and ``self.X`` attrs) bound to jax.jit /
    register_program results, with the literal static spec of each
    binding's jit call — the call-site half of the retrace analysis."""
    bound: Dict[str, Tuple[Set[int], Set[str]]] = {}
    self_bound: Dict[str, Tuple[Set[int], Set[str]]] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Assign) or \
                not isinstance(node.value, ast.Call):
            continue
        call = node.value
        if not (_is_jax_jit(ctx, call.func) or
                _program_fn_arg(ctx, call) is not None):
            continue
        st = _literal_static_spec(call)
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                bound[tgt.id] = st
            elif isinstance(tgt, ast.Attribute) and \
                    isinstance(tgt.value, ast.Name) and \
                    tgt.value.id == "self":
                self_bound[tgt.attr] = st
    return bound, self_bound


def _scalar_literal(node: ast.AST):
    """The python numeric value of a literal operand, through unary
    sign (``-1.0`` parses as UnaryOp(USub, Constant)); None otherwise.
    bools are excluded (two values cannot amplify retraces)."""
    sign = 1
    if isinstance(node, ast.UnaryOp) and \
            isinstance(node.op, (ast.USub, ast.UAdd)):
        sign = -1 if isinstance(node.op, ast.USub) else 1
        node = node.operand
    if isinstance(node, ast.Constant) and \
            isinstance(node.value, (int, float)) and \
            not isinstance(node.value, bool):
        return sign * node.value
    return None


@register_rule
class RetraceHazard(Rule):
    id = "retrace-hazard"
    description = ("per-call-site retrace amplifiers on the hot-path "
                   "surfaces whose zero-retrace behavior is contracted "
                   "(step, serve, batcher, programs): python branches on "
                   "a traced argument's .shape/.ndim inside a jitted "
                   "body (each distinct shape compiles a separate "
                   "executable — close the shape set or hoist the "
                   "branch), and python scalar literals passed as traced "
                   "operands at jit call sites in hot-path roots (the "
                   "program cache keys scalars by VALUE, so every "
                   "distinct scalar is a fresh compile).  Per-op eager "
                   "kernels (mxnet_tpu/ops) are exempt: rank/shape "
                   "specialization is their light-census contract")
    invariant_from = "ISSUE 11 (program contracts: static zero-retrace)"

    # scoped to the files whose dispatch behavior the contracts lane
    # proves — the same surface the host-sync rule roots
    path_patterns = tuple(sorted({pat for pat, _ in HOT_PATH_ROOTS}))

    _SHAPE_ATTRS = ("shape", "ndim", "size")

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        yield from self._shape_branches(ctx)
        yield from self._scalar_call_sites(ctx)

    # -- (a) shape-specializing branches inside traced bodies ---------------
    def _shape_branches(self, ctx: FileContext) -> Iterator[Diagnostic]:
        for fn, jit_call in sorted(_collect_jit_functions(ctx).items(),
                                   key=lambda kv: kv[0].lineno):
            static = _static_param_names(fn, jit_call)
            params = {a.arg for a in fn.args.args} | \
                {a.arg for a in getattr(fn.args, "posonlyargs", [])}
            traced = params - static
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                names = self._shape_reads(node.test, traced)
                if names:
                    yield ctx.diag(
                        self.id, node,
                        "branch on %s inside jitted %r specializes the "
                        "executable per input shape — every new shape "
                        "is a silent recompile; bucket the shapes "
                        "(declare a contract closure), mark the "
                        "argument static, or hoist the branch" %
                        (", ".join(sorted(names)), fn.name))

    def _shape_reads(self, test: ast.AST, traced: Set[str]) -> Set[str]:
        """'x.shape...' chains rooted at a traced parameter inside a
        branch test — through subscripts too (``xs[0].shape[0]``: the
        tuple-of-batches layout every window body uses)."""
        out: Set[str] = set()
        for node in ast.walk(test):
            if not isinstance(node, ast.Attribute) or \
                    node.attr not in self._SHAPE_ATTRS:
                continue
            base = node.value
            while isinstance(base, ast.Subscript):
                base = base.value
            if isinstance(base, ast.Name) and base.id in traced:
                out.add("%s.%s" % (base.id, node.attr))
        return out

    # -- (b) python scalars as traced operands in hot-path roots ------------
    def _scalar_call_sites(self, ctx: FileContext) -> Iterator[Diagnostic]:
        roots: List[str] = []
        for pat, quals in HOT_PATH_ROOTS:
            if not fnmatch.fnmatch(ctx.path, pat):
                continue
            for qual in ctx.functions:
                if any(fnmatch.fnmatch(qual, qp) for qp in quals):
                    roots.append(qual)
        if not roots:
            return
        bound, self_bound = _jit_call_bindings(ctx)
        hot = ctx.reachable_from(roots)
        for qual in sorted(hot):
            fn = ctx.functions[qual]
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                st = None
                f = node.func
                if isinstance(f, ast.Name) and f.id in bound:
                    st = bound[f.id]
                elif isinstance(f, ast.Attribute) and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id == "self" and f.attr in self_bound:
                    st = self_bound[f.attr]
                elif isinstance(f, ast.Call) and \
                        (_is_jax_jit(ctx, f.func) or
                         _program_fn_arg(ctx, f) is not None):
                    st = _literal_static_spec(f)
                if st is None:
                    continue
                static_nums, static_names = st
                hits = []
                for pos, arg in enumerate(node.args):
                    if pos in static_nums:
                        continue
                    val = _scalar_literal(arg)
                    if val is not None:
                        hits.append((val, arg))
                for kw in node.keywords:
                    if kw.arg is None or kw.arg in static_names:
                        continue
                    val = _scalar_literal(kw.value)
                    if val is not None:
                        hits.append((val, kw.value))
                for val, anchor in hits:
                    yield ctx.diag(
                        self.id, anchor,
                        "python scalar %r passed as a traced operand "
                        "of a jitted call in %s (hot path): the "
                        "program cache keys scalars by VALUE — each "
                        "distinct value retraces; pass a jnp array "
                        "or mark the position static"
                        % (val, qual))


# ---------------------------------------------------------------------------
# wire-manifest-schema (PR 19 satellite): the four shipped protocol
# machines must declare their WIRE_VERBS through the shared
# declare_verbs() schema helper — a bare dict has no vocabulary
# validation and is invisible to the --protocol verifier.
# ---------------------------------------------------------------------------

@register_rule
class WireManifestSchema(Rule):
    id = "wire-manifest-schema"
    description = ("shipped WIRE_VERBS manifests must go through "
                   "kvstore.wire_verbs.declare_verbs (schema-validated, "
                   "protocol-verifier visible), not a bare dict")
    invariant_from = "PR 19"
    path_patterns = ("mxnet_tpu/kvstore/server.py",
                     "mxnet_tpu/serve/server.py",
                     "mxnet_tpu/serve/router.py",
                     "mxnet_tpu/fleet.py")

    def check(self, ctx: FileContext) -> Iterator[Diagnostic]:
        for node in ast.walk(ctx.tree):
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                target = node.targets[0].id
            elif isinstance(node, ast.AnnAssign) and \
                    isinstance(node.target, ast.Name):
                target = node.target.id
            if target != "WIRE_VERBS":
                continue
            val = getattr(node, "value", None)
            is_declared = (isinstance(val, ast.Call) and
                           _attr_chain(val.func) is not None and
                           _attr_chain(val.func)[-1] == "declare_verbs")
            if not is_declared:
                yield ctx.diag(
                    self.id, node,
                    "WIRE_VERBS here must be built by declare_verbs() "
                    "from mxnet_tpu/kvstore/wire_verbs.py — a bare "
                    "dict skips schema validation and hides this "
                    "machine from `python -m tools.mxlint --protocol`")


# registered last so --list-rules / --select see the --protocol lane's
# rule ids (scope='protocol': skipped by the file and project passes,
# executed only inside tools/mxlint/protocol.py's check_sources)
from . import protocol as _protocol  # noqa: E402,F401
