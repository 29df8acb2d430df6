"""XLA program census: per-program compile-cost/memory accounting, a
retrace explainer, and a device-buffer census (ISSUE 10 tentpole).

The runtime could already trace *time* (telemetry spans) and *wire
bytes*, but its ~10 scattered ``jax.jit`` sites compiled blind: nothing
recorded compile latency, XLA cost, or device-memory footprint, and the
ROADMAP's FSDP acceptance ("per-chip memory dropping ~linearly with the
fsdp axis") was unmeasurable.  The Julia→TPU whole-program work (arxiv
1810.09868) and TF's cost-model surfaces (arxiv 1605.08695) both treat
compile cost and program footprint as first-class pipeline outputs —
this module is that layer:

* **Program registry** — every jit-creation site routes through
  :func:`register_program`, which returns a :class:`Program` wrapper.
  ``mode='aot'`` owns its executable cache explicitly
  (``jit(fn).lower(args).compile()``), so compile wall-time is bracketed
  exactly and the compiled object's ``memory_analysis()`` (argument /
  output / temp / generated-code bytes) and ``cost_analysis()`` (flops,
  bytes accessed) are captured where the backend provides them —
  explicitly ``None`` where it does not.  ``mode='light'`` keeps
  ``jax.jit``'s C++ dispatch for ultra-hot sites (eager per-op kernels,
  hybridize cache) and detects (re)traces with a zero-cost trace probe;
  compile time is the bracketed dispatch that traced.  Per-program
  numbers feed the telemetry registry —
  ``program_compile_seconds{program}``, ``program_temp_bytes{program}``,
  ``program_flops{program}``, ``program_retraces{program}`` — and ride
  the Prometheus/JSON exposition.

* **Retrace explainer** — each program record keeps the last trace
  signature (input avals + tree structure); on a retrace the structured
  diff (which arg's shape/dtype/weak-type changed, or that the tree
  structure itself did) is logged and recorded, so the serving
  zero-retrace gate and CompiledStep invalidations are diagnosable
  instead of just countable.

* **Device-buffer census** — :func:`buffer_census` buckets
  ``jax.live_arrays()`` by owner (params / optimizer_state /
  ef_residuals / serve / other; owners self-register via
  :func:`track_buffers`), and :class:`LeakDetector` turns step-over-step
  monotonic growth beyond ``MX_LEAK_WARN_BYTES`` into a gauge + warning,
  wired into the flight recorder (periodic step observer) and crash
  dumps (telemetry crash sections).

* **Program contracts** (ISSUE 11) — the registry's declarative face:
  :func:`declare_contract` lets each jit site state its abstract input
  signatures (``jax.ShapeDtypeStruct`` trees), expected donation set,
  temp-HBM budget and optionally a trace-closure spec.  Builders are
  lazy (declaring costs a dict insert); ``python -m tools.mxlint
  --contracts`` (tools/mxlint/contracts.py) lowers every declared case
  device-free and proves donation aliasing, the HBM budget and closure
  — see docs/TESTING.md §5.

Hot-path contract (mxlint-rooted): :meth:`Program.__call__`,
:func:`signature_of` and :meth:`ProgramRecord.note_compile` are
dispatch-time bookkeeping only — they read shapes/avals and never sync a
device; the census walk itself reads ``nbytes`` off live array handles
(host metadata, no transfer) and runs only periodically / at crash time.
"""
from __future__ import annotations

import functools
import logging
import re
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from .base import get_env, recompute_counting, recompute_tally
from . import compile_cache as _cc
from . import telemetry as _telemetry

__all__ = [
    "register_program", "Program", "ProgramRecord", "census_enabled",
    "program_table", "program_summary", "program_memory_bytes",
    "find_record", "reset_records", "module_name", "program_scopes",
    "signature_of", "diff_signatures",
    "track_buffers", "buffer_census", "leak_detector", "LeakDetector",
    "CENSUS_OWNERS",
    "CONTRACT_SCHEMA", "ContractCase", "ContractClosure",
    "ProgramContract", "declare_contract", "contracts",
    "contract_manifest", "reset_contracts",
]

logger = logging.getLogger("mxnet_tpu.programs")


def census_enabled() -> bool:
    """MX_PROGRAM_CENSUS (default on): program registry + buffer census."""
    return bool(get_env("MX_PROGRAM_CENSUS", dtype=bool))


# ---------------------------------------------------------------------------
# Trace signatures + the retrace explainer
# ---------------------------------------------------------------------------

def _leaf_sig(x):
    """One leaf's trace identity.  jax arrays/tracers contribute their
    aval (shape, dtype, weak_type) plus their sharding — exactly
    jax.jit's cache key; an AOT executable strictly rejects inputs on a
    different device, so the device must key the cache too.
    ndarray-likes contribute a (shape, dtype) tuple; python scalars
    their VALUE (conservative: correct under static_argnums, and no
    routed site passes scalars as traced operands)."""
    aval = getattr(x, "aval", None)
    if aval is not None:
        return ("aval", aval, getattr(x, "sharding", None))
    shape = getattr(x, "shape", None)
    if shape is not None and hasattr(x, "dtype"):
        return ("arr", tuple(int(s) for s in shape), str(x.dtype))
    if isinstance(x, (bool, int, float, complex, str, bytes)):
        return ("py", type(x).__name__, x)
    return ("obj", type(x).__name__)


def signature_of(args: tuple, kwargs: Optional[dict] = None) -> Tuple:
    """(treedef, per-leaf identity) of a call — the program cache key.
    Reads shapes/avals only; never touches device data."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    return (treedef, tuple(_leaf_sig(x) for x in leaves))


def _avals_of(args: tuple, kwargs: dict) -> Tuple:
    """What a trace depends on, whether it is given arrays, numpy ones,
    Python scalars or tracers; a static argument stands for itself."""
    def aval(x):
        try:
            return jax.typeof(x)
        except TypeError:
            return x
    return tuple(aval(x) for x in jax.tree_util.tree_leaves((args, kwargs)))


class _SigLeaf:
    """Opaque wrapper so a stored leaf signature (which may itself be a
    tuple) survives tree_unflatten as ONE leaf when the explainer
    rebuilds arg paths."""

    __slots__ = ("sig",)

    def __init__(self, sig):
        self.sig = sig


def _leaf_desc(sig) -> Dict[str, Any]:
    """Human/JSON form of one leaf signature."""
    if isinstance(sig, tuple) and sig and sig[0] == "aval":
        _, aval, sharding = sig
        out = {"shape": tuple(int(s) for s in aval.shape),
               "dtype": str(aval.dtype),
               "weak_type": bool(getattr(aval, "weak_type", False))}
        if sharding is not None:
            out["device"] = str(sharding)
        return out
    if isinstance(sig, tuple) and sig and sig[0] == "arr":
        return {"shape": sig[1], "dtype": sig[2], "weak_type": False}
    if isinstance(sig, tuple) and sig and sig[0] == "py":
        return {"py": sig[1], "value": sig[2]}
    return {"opaque": str(sig)}


def _paths_for(treedef, sigs) -> List[str]:
    tree = jax.tree_util.tree_unflatten(treedef,
                                        [_SigLeaf(s) for s in sigs])
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, _SigLeaf))
    return [jax.tree_util.keystr(path) for path, _ in flat]


def diff_signatures(old: Tuple, new: Tuple) -> Optional[Dict[str, Any]]:
    """Structured explanation of why `new` could not reuse `old`'s
    executable: either the argument tree structure changed, or specific
    leaves changed shape/dtype/weak-type.  None when identical."""
    if old == new:
        return None
    old_td, old_sigs = old
    new_td, new_sigs = new
    if old_td != new_td:
        return {"kind": "tree_structure",
                "before": str(old_td), "after": str(new_td)}
    paths = _paths_for(new_td, new_sigs)
    changed = []
    for path, a, b in zip(paths, old_sigs, new_sigs):
        if a == b:
            continue
        da, db = _leaf_desc(a), _leaf_desc(b)
        if da.get("dtype") != db.get("dtype") and \
                da.get("shape") == db.get("shape"):
            change = "dtype"
        elif da.get("shape") != db.get("shape") and \
                da.get("dtype") == db.get("dtype"):
            change = "shape"
        elif da.get("shape") == db.get("shape") and \
                da.get("dtype") == db.get("dtype") and \
                da.get("device") != db.get("device"):
            # same logical value, different placement: a LAYOUT change.
            # When the device set is unchanged but the partitioning is
            # (same mesh, new PartitionSpec — the FSDP resharding path),
            # call it what it is: a sharding change, not a device move.
            change = "device"
            try:
                sa = a[2] if isinstance(a, tuple) and a[0] == "aval" else None
                sb = b[2] if isinstance(b, tuple) and b[0] == "aval" else None
                if sa is not None and sb is not None and \
                        getattr(sa, "device_set", None) == \
                        getattr(sb, "device_set", None):
                    change = "sharding"
            except Exception:
                pass
        else:
            change = "leaf"
        changed.append({"arg": path, "change": change,
                        "before": da, "after": db})
    return {"kind": "leaves", "changed": changed}


def _format_diff(diff: Dict[str, Any]) -> str:
    if diff["kind"] == "tree_structure":
        return "argument tree structure changed: %s -> %s" % (
            diff["before"], diff["after"])
    parts = []
    for c in diff["changed"][:8]:
        parts.append("%s %s: %s -> %s" % (
            c["arg"], c["change"], c["before"], c["after"]))
    more = len(diff["changed"]) - 8
    if more > 0:
        parts.append("(+%d more)" % more)
    return "; ".join(parts)


# ---------------------------------------------------------------------------
# Program records
# ---------------------------------------------------------------------------

def _memory_dict(compiled) -> Optional[Dict[str, Any]]:
    """CompiledMemoryStats → plain dict, or None where the backend does
    not provide memory_analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    try:
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except AttributeError:
        return None


def _cost_dict(compiled) -> Optional[Dict[str, Any]]:
    """cost_analysis() → {flops, bytes_accessed}, or None."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out: Dict[str, Any] = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out or None


class ProgramRecord:
    """Aggregated accounting for one named program (all its wrapper
    instances and executables).

    ``specializing`` records (ISSUE 13): some sites aggregate MANY
    expected shape/rank specializations under one name — per-op eager
    kernels, the hybridize cache, the fused optimizer's per-model tree
    kernels.  For those, a FRESH signature is an expected
    specialization (counted separately), and ``retraces`` counts only
    a rebuild of an ALREADY-SEEN signature — genuine cache thrash.
    Strict records (the default: step/serve/mesh programs) keep the
    original semantics: any signature change is a retrace."""

    def __init__(self, name: str, mode: str, specializing: bool = False):
        self.name = name
        self.mode = mode
        self.specializing = bool(specializing)
        self._lock = threading.Lock()
        self.compiles = 0                 # executables built
        self.retraces = 0                 # compiles whose signature
        #                                   differed from the last seen
        #                                   (specializing: re-compiles
        #                                   of a KNOWN signature)
        self.specializations = 0          # fresh-signature compiles of
        #                                   a specializing program
        self._seen_sigs: set = set()
        self.compile_seconds_total = 0.0
        self.compile_seconds_max = 0.0
        self.last_compile_seconds: Optional[float] = None
        self.memory: Optional[Dict[str, Any]] = None    # latest compile's
        self.cost: Optional[Dict[str, Any]] = None
        self.temp_bytes_peak: Optional[int] = None
        self.last_sig: Optional[Tuple] = None
        self.last_retrace: Optional[Dict[str, Any]] = None
        # what the recomputed blocks of the latest trace keep for their
        # backward pass (base.recompute_keep)
        self.recompute_kept_values = 0
        self.recompute_kept_bytes = 0
        # newest AOT executable (alive in Program._cache anyway): what
        # program_scopes() reads, on demand only
        self.executable = None
        labels = {"program": name}
        reg = _telemetry.registry
        self._h_compile = reg.histogram(
            "program_compile_seconds",
            doc="wall-clock trace+lower+compile time per XLA program",
            labels=labels)
        self._c_retrace = reg.counter(
            "program_retraces",
            doc="program rebuilds whose input signature changed vs the "
                "previous trace (see the retrace explainer log)",
            labels=labels)
        self._g_temp = reg.gauge(
            "program_temp_bytes",
            doc="XLA memory_analysis temp allocation of the latest "
                "executable", labels=labels)
        self._g_flops = reg.gauge(
            "program_flops",
            doc="XLA cost_analysis flops of the latest executable",
            labels=labels)

    def _absorb_metadata_locked(self, mem, cost) -> None:
        """Fold one executable's memory/cost analysis into the record
        (caller holds self._lock)."""
        if mem is not None:
            self.memory = mem
            tb = mem["temp_bytes"]
            if self.temp_bytes_peak is None or tb > self.temp_bytes_peak:
                self.temp_bytes_peak = tb
        if cost is not None:
            self.cost = cost

    def _publish_metadata_gauges(self, mem, cost) -> None:
        if mem is not None:
            self._g_temp.set(mem["temp_bytes"])
        if cost is not None and "flops" in cost:
            self._g_flops.set(cost["flops"])

    def note_compile(self, seconds: float, sig: Tuple,
                     compiled=None, kept=None) -> None:
        """Record one executable build: timing, optional AOT metadata,
        the retrace explainer's signature diff, and what the trace's
        recomputed blocks keep (`kept`: a ``base.recompute_tally``)."""
        mem = _memory_dict(compiled) if compiled is not None else None
        cost = _cost_dict(compiled) if compiled is not None else None
        diff = None
        is_retrace = False
        with self._lock:
            self.compiles += 1
            self.compile_seconds_total += seconds
            if seconds > self.compile_seconds_max:
                self.compile_seconds_max = seconds
            self.last_compile_seconds = seconds
            if self.last_sig is not None:
                diff = diff_signatures(self.last_sig, sig)
                if diff is not None:
                    if self.specializing and sig not in self._seen_sigs:
                        # fresh shape at a specializing site: expected
                        # (per-op rank/shape specialization is the
                        # light-census contract), counted separately
                        self.specializations += 1
                    else:
                        is_retrace = True
                        self.retraces += 1
                        self.last_retrace = {"diff": diff,
                                             "compile_seconds": seconds}
            self._seen_sigs.add(sig)
            self.last_sig = sig
            if compiled is not None:
                self.executable = compiled
            if kept is not None:
                self.recompute_kept_values = kept.values
                self.recompute_kept_bytes = kept.bytes
            self._absorb_metadata_locked(mem, cost)
        self._h_compile.observe(seconds)
        self._publish_metadata_gauges(mem, cost)
        if is_retrace:
            self._c_retrace.inc()
            logger.info("program %r retraced (compile %.3fs): %s",
                        self.name, seconds, _format_diff(diff))
        elif diff is not None:
            logger.debug("program %r specialized (compile %.3fs): %s",
                         self.name, seconds, _format_diff(diff))

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "name": self.name,
                "mode": self.mode,
                "specializing": self.specializing,
                "compiles": self.compiles,
                "retraces": self.retraces,
                "specializations": self.specializations,
                "compile_seconds": {
                    "total": round(self.compile_seconds_total, 6),
                    "max": round(self.compile_seconds_max, 6),
                    "last": None if self.last_compile_seconds is None
                    else round(self.last_compile_seconds, 6),
                },
                "memory": dict(self.memory) if self.memory else None,
                "cost": dict(self.cost) if self.cost else None,
                "temp_bytes_peak": self.temp_bytes_peak,
                "recompute_kept_values": self.recompute_kept_values,
                "recompute_kept_bytes": self.recompute_kept_bytes,
                "last_retrace": self.last_retrace,
            }


_records_lock = threading.Lock()
_records: Dict[str, ProgramRecord] = {}


def _record(name: str, mode: str,
            specializing: bool = False) -> ProgramRecord:
    with _records_lock:
        rec = _records.get(name)
        if rec is None:
            rec = ProgramRecord(name, mode, specializing=specializing)
            _records[name] = rec
    return rec


def find_record(name: str) -> Optional[ProgramRecord]:
    with _records_lock:
        return _records.get(name)


def program_table() -> Dict[str, Dict[str, Any]]:
    """{program name: record snapshot} — what crash dumps carry."""
    with _records_lock:
        recs = list(_records.values())
    return {rec.name: rec.snapshot() for rec in recs}


def program_summary() -> Dict[str, Any]:
    """Roll-up across every registered program: total compile seconds,
    total retraces, peak temp bytes — what the benchmark's driver reads
    (``compiles``, ``compile_seconds_total``) — and what the programs'
    recomputed blocks keep (``gluon.Block.recompute``)."""
    table = program_table()
    total_s = sum(t["compile_seconds"]["total"] for t in table.values())
    peak_temp = [t["temp_bytes_peak"] for t in table.values()
                 if t["temp_bytes_peak"] is not None]
    return {
        "programs": len(table),
        "compiles": sum(t["compiles"] for t in table.values()),
        "retraces": sum(t["retraces"] for t in table.values()),
        "specializations": sum(t["specializations"]
                               for t in table.values()),
        "compile_seconds_total": round(total_s, 6),
        "peak_temp_bytes": max(peak_temp) if peak_temp else None,
        "recompute_kept_values": sum(t["recompute_kept_values"]
                                     for t in table.values()),
        "recompute_kept_bytes": sum(t["recompute_kept_bytes"]
                                    for t in table.values()),
    }


def program_memory_bytes(prefix: str) -> Dict[str, int]:
    """Aggregate ``memory_analysis`` bytes over every registered
    program whose name starts with ``prefix`` (ISSUE 20): the HBM
    bin-packer's per-model program-side footprint.  ``temp_bytes_peak``
    is the max transient allocation any one of the model's programs
    needs live at dispatch (programs run one at a time per replica);
    argument/output bytes are informational — the live arrays they
    alias are already counted by :func:`buffer_census`."""
    table = program_table()
    out = {"programs": 0, "temp_bytes_peak": 0,
           "argument_bytes_max": 0, "output_bytes_max": 0}
    for name, t in table.items():
        if not name.startswith(prefix):
            continue
        out["programs"] += 1
        tb = t.get("temp_bytes_peak")
        if tb:
            out["temp_bytes_peak"] = max(out["temp_bytes_peak"],
                                         int(tb))
        mem = t.get("memory") or {}
        for src, dst in (("argument_bytes", "argument_bytes_max"),
                         ("output_bytes", "output_bytes_max")):
            b = mem.get(src)
            if b:
                out[dst] = max(out[dst], int(b))
    return out


def program_count() -> int:
    with _records_lock:
        return len(_records)


def reset_records() -> None:
    """Drop every record (tests).  Telemetry instruments persist —
    readers should use fresh names or deltas."""
    with _records_lock:
        _records.clear()


# ---------------------------------------------------------------------------
# Module names and op scopes: what a device trace is joined to
# ---------------------------------------------------------------------------

def module_name(name: str) -> str:
    """The name a program's jitted function carries, so that its XLA
    module (jax prefixes ``jit_``) is called what the census calls it:
    ``step.step`` -> ``mx_step_step`` (module ``jit_mx_step_step``)."""
    return "mx_" + re.sub(r"[^0-9A-Za-z]+", "_", name).strip("_")


# the step program's top-level scopes (step.py); an op under `forward`
# whose path passes through transpose(...) belongs to the backward pass
TOP_SCOPES = ("forward", "backward", "exchange", "optimizer", "metric")

_HLO_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(ROOT\s+)?%?([^\s=]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([^\s,)}]+)")
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_HLO_NO_WORK = frozenset(("constant", "parameter", "tuple",
                          "get-tuple-element", "bitcast", "broadcast",
                          "iota", "reshape"))


def top_scope(path: str) -> Optional[str]:
    """Which of :data:`TOP_SCOPES` an ``op_name`` path lies under, or
    None: the first component that is no ``jit(...)`` wrapper and no
    loop of a scan window, with jax's transform wrappers
    (``jvp(forward)``) taken off."""
    for part in path.split("/"):
        if not part or part.startswith(("jit(", "pjit(")) \
                or part in ("while", "body", "cond"):   # a scan window's loop
            continue
        base = part[part.rfind("(") + 1:].rstrip(")") if "(" in part \
            else part
        if base == "forward":
            return "backward" if "transpose(" in path else "forward"
        return base if base in TOP_SCOPES else None
    return None


def _parse_scopes(text: str) -> Dict[str, Any]:
    """:func:`program_scopes` of one compiled module's HLO text."""
    module = text.split(None, 2)[1].rstrip(",") \
        if text.startswith("HloModule") else None
    computations: Dict[str, List[Tuple]] = {}
    entry = current = None
    for line in text.splitlines():
        if current is None:
            m = _HLO_COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        op_name = _HLO_OP_NAME.search(line)
        called = _HLO_CALLED.findall(line)
        branches = _HLO_BRANCHES.search(line)
        if branches:
            called += [b.strip().lstrip("%")
                       for b in branches.group(1).split(",")]
        current.append((m.group(2), m.group(3), bool(m.group(1)),
                        op_name.group(1) if op_name else "", called))
    instructions: Dict[str, Dict[str, Any]] = {}
    seen = set()

    def tops_of(computation):
        """Top-level scopes of the work a fused computation holds (XLA
        shares one constant between scopes: such an instruction's name
        says nothing about the fusion)."""
        found = set()
        for _n, op, _root, path, called in computations.get(
                computation, ()):
            if op in _HLO_NO_WORK:
                continue
            top = top_scope(path)
            if top:
                found.add(top)
            for c in called:
                found |= tops_of(c)
        return found

    def walk(computation):
        if computation in seen:
            return
        seen.add(computation)
        for name, op, _root, path, called in computations.get(
                computation, ()):
            tops = set()
            if op == "fusion":
                for c in called:
                    root = [i for i in computations.get(c, ()) if i[2]]
                    if root and root[0][3]:
                        path = root[0][3]
                    tops |= tops_of(c)
            else:
                for c in called:       # while / conditional / call bodies
                    walk(c)
            top = top_scope(path)
            # backward has no scope of its own: a fusion of forward and
            # transposed ops lies under ONE scope step.py opens, `forward`
            opened = {"forward" if t == "backward" else t for t in tops}
            instructions[name] = {"scope": path, "top": top,
                                  "tops": sorted(tops | ({top} - {None})),
                                  "mixed": len(opened) > 1}

    if entry is not None:
        walk(entry)
    return {"module": module, "instructions": instructions}


def program_scopes(name: str) -> Optional[Dict[str, Any]]:
    """Where each instruction of program `name`'s newest executable comes
    from, parsed from its compiled text ON DEMAND (never at compile
    time); None when the program has no AOT executable::

        {"module": "<hlo module name>",
         "instructions": {"<instr>": {"scope": "<op_name path>",
                                      "top": one of TOP_SCOPES or None,
                                      "tops": [...], "mixed": bool}}}

    Every instruction of the entry computation and of the ``while`` /
    ``conditional`` / ``call`` bodies it reaches.  A fusion takes the
    ``op_name`` of its root; ``tops`` lists the top-level scopes its
    fused instructions fall under and ``mixed`` says they are under more
    than one of the scopes step.py opens (``backward`` is no scope of its
    own: it is what ``forward`` becomes under ``transpose(``).  This is what a device trace's op rows are joined to by
    instruction name: it needs nothing of what the trace itself
    carries."""
    rec = find_record(name)
    compiled = rec.executable if rec is not None else None
    if compiled is None:
        return None
    return _parse_scopes(compiled.as_text())


# ---------------------------------------------------------------------------
# The wrapper
# ---------------------------------------------------------------------------

class Program:
    """Census-wrapped jitted callable.

    ``aot=True``: per-signature executable cache via
    ``jit.lower(...).compile()`` — exact compile bracketing + XLA
    memory/cost metadata.  Falls back permanently to plain jit dispatch
    if the site cannot lower ahead-of-time (exotic shardings etc.).

    ``aot=False`` (light): ``jax.jit`` keeps its C++ dispatch; a trace
    probe inside the traced fn bumps a counter, so a dispatch that
    traced is detected after the fact and its wall time recorded as the
    compile cost (memory/cost stay explicitly None).
    """

    def __init__(self, name: str, mode: str, fn: Callable,
                 jit_kw: Dict[str, Any], aot: bool,
                 specializing: bool = False):
        self._name = name
        self._mode = mode
        self._specializing = bool(specializing)
        self._fn = fn            # compile-cache function fingerprint
        self._record: Optional[ProgramRecord] = None
        self._seq = 0
        self._noted = 0     # compiles already recorded (under _cache_lock)
        # what each trace tags base.recompute_keep, by the avals it was
        # traced for: an AOT program's goes into its census row, a light
        # one's to the program that calls it inside a recomputed block
        self._kept: Dict[Tuple, Any] = {}

        def _trace_probe(*a, **k):
            # runs at TRACE time only (host side); the attribute write
            # is the point — it marks "this dispatch compiled"
            self._seq += 1
            with recompute_tally(always=not aot) as kept:
                self._kept[_avals_of(a, k)] = kept
                return fn(*a, **k)

        functools.update_wrapper(_trace_probe, fn, updated=())
        # the XLA module is called what the census calls the program
        # (jax prefixes "jit_"): trace "XLA Modules" events, HLO dumps
        # and jax's persistent-cache entries all carry the registry name
        _trace_probe.__name__ = _trace_probe.__qualname__ = \
            module_name(name)
        self._jit = jax.jit(_trace_probe, **jit_kw)
        self._jit_kw = dict(jit_kw)
        self._aot = aot
        self._cache: Dict[Tuple, Any] = {}
        self._cache_lock = threading.Lock()

    @property
    def jit_kw(self) -> Dict[str, Any]:
        """The jit kwargs this site registered with (donate_argnums,
        static_argnums, shardings) — what the contract verifier proves
        against."""
        return dict(self._jit_kw)

    def lower(self, *args, **kwargs):
        """AOT-lower the wrapped jit without dispatching — the contract
        verifier's device-free entry point (works with
        jax.ShapeDtypeStruct trees; no buffers are materialized)."""
        return self._jit.lower(*args, **kwargs)

    @property
    def record(self) -> ProgramRecord:
        """Get-or-create LAZILY at first compile: a registered-but-never-
        dispatched wrapper (e.g. a module-level kernel the workload never
        runs) must not pollute the table with a zero-compile row."""
        if self._record is None:
            self._record = _record(self._name, self._mode,
                                   specializing=self._specializing)
        return self._record

    @property
    def executables(self) -> int:
        with self._cache_lock:
            return len(self._cache)

    def _compile(self, sig, args, kwargs):
        """Lower, then compile or load, each under the census name: the
        spans ``compile.lower`` (jax's own trace and lowering spans lie
        inside it) and ``compile.cache_load`` / ``compile.backend``
        around what ``.compile()`` does besides jax's backend span."""
        tags = {"fun_name": module_name(self._name), "program": self._name}
        t0 = time.perf_counter()
        try:
            lowered = self._jit.lower(*args, **kwargs)
            t_lowered, wall = time.perf_counter(), time.time()
            _telemetry.record_span("compile.lower", t0, t_lowered,
                                   cat="compile", **tags)
            compiled = lowered.compile()
            _telemetry.record_span(
                "compile.cache_load" if _cc.hit_between(wall, time.time())
                else "compile.backend", t_lowered, time.perf_counter(),
                cat="compile", **tags)
        except Exception as e:
            # this site cannot AOT-lower (e.g. layout/sharding the
            # lowering path rejects): census degrades to light mode.
            # The failed lower may still have TRACED (bumping the probe)
            # — consume those bumps so the light path only counts its
            # own subsequent trace, not phantom compiles.
            with self._cache_lock:
                self._noted = self._seq
            self._aot = False
            logger.info("programs: AOT census unavailable for %r (%s: "
                        "%s); using plain jit dispatch",
                        self._name, type(e).__name__, e)
            return None
        dt = time.perf_counter() - t0
        with self._cache_lock:
            kept = self._cache.setdefault(sig, compiled)
            self._noted = self._seq     # AOT owns these probe bumps
        if kept is compiled:
            # two racing cold-callers both compile; the one whose
            # executable the cache kept records the build — compiles
            # stays exact
            self.record.note_compile(
                dt, sig, compiled=kept,
                kept=self._kept.get(_avals_of(args, kwargs)))
        return kept

    def ensure_compiled(self, *args, **kwargs):
        """Build the executable for this argument signature WITHOUT
        dispatching it.

        Returns a truthy provenance string when an AOT executable is
        ready — ``"compiled"`` (built now) or ``"ready"`` (already in
        the in-memory table) — and False in light mode or after an AOT
        fallback, where the caller must dispatch normally."""
        if not self._aot:
            return False
        sig = signature_of(args, kwargs)
        with self._cache_lock:
            if sig in self._cache:
                return "ready"
        if self._compile(sig, args, kwargs) is None:
            return False
        return "compiled"

    def __call__(self, *args, **kwargs):
        if self._aot:
            sig = signature_of(args, kwargs)
            compiled = self._cache.get(sig)
            if compiled is None:
                compiled = self._compile(sig, args, kwargs)
            if compiled is not None:
                return compiled(*args, **kwargs)
        seq = self._seq
        t0 = time.perf_counter()
        out = self._jit(*args, **kwargs)
        if self._seq != seq:
            dt = time.perf_counter() - t0
            # claim the trace under the lock: two threads dispatching
            # concurrently both observe the bump, but only the first
            # records it — no double-counted compiles / phantom retraces
            with self._cache_lock:
                claimed = self._seq - self._noted
                self._noted = self._seq
            for _ in range(claimed):
                self.record.note_compile(dt, signature_of(args, kwargs))
        outer = recompute_counting()
        if outer is not None:
            # traced now or handed an earlier trace: the same tags
            kept = self._kept.get(_avals_of(args, kwargs))
            if kept is not None:
                outer.join(kept.values, kept.bytes)
        return out


def register_program(name: str, fn: Callable, mode: str = "aot",
                     specializing: bool = False, **jit_kw) -> Callable:
    """Route one jit-creation site through the program census.

    Drop-in for ``jax.jit(fn, **jit_kw)``; returns a callable.  ``name``
    is the program's stable registry identity (wrappers sharing a name
    aggregate into one record — e.g. every hybridize cache entry of one
    block class).  ``mode='aot'`` for programs built once and dispatched
    per step/batch; ``mode='light'`` for per-op hot paths.
    ``specializing=True`` marks a site whose record expects many
    shape/rank specializations under one name (per-op kernels, the
    hybridize cache, fused optimizer tree kernels): fresh signatures
    count as ``specializations``, and ``retraces`` counts only genuine
    rebuilds of an already-seen signature.  With
    ``MX_PROGRAM_CENSUS=0`` this is exactly ``jax.jit``.
    """
    _cc.activate()              # idempotent; arms jax's persistent cache
    if not census_enabled():
        return jax.jit(fn, **jit_kw)
    return Program(name, mode, fn, jit_kw, aot=(mode == "aot"),
                   specializing=specializing)


# ---------------------------------------------------------------------------
# Program contracts (ISSUE 11): the registry's declarative face
# ---------------------------------------------------------------------------

# bumped when the manifest JSON layout changes; python -m tools.mxlint
# --check-manifest validates checked-in manifests against this version
CONTRACT_SCHEMA = 1


class ContractCase:
    """One concrete, device-free lowering of a contracted program.

    ``args``/``kwargs`` are abstract input trees (``jax.ShapeDtypeStruct``
    leaves — no buffers); ``target`` is the site's own registered wrapper
    (anything with ``.lower``, i.e. a :class:`Program` or a ``jax.jit``
    object) so the verifier proves the EXACT jit spec the runtime ships.
    Alternatively ``fn``+``jit_kw`` hand the verifier a raw traceable
    body to jit itself (the kvstore exchange bodies, which normally
    inline into the step program, are contracted standalone this way).
    """

    __slots__ = ("program", "label", "target", "fn", "jit_kw", "args",
                 "kwargs")

    def __init__(self, program: str, args: tuple, kwargs=None,
                 label: Optional[str] = None, target=None,
                 fn: Optional[Callable] = None, jit_kw=None):
        if (target is None) == (fn is None):
            raise ValueError("ContractCase needs exactly one of "
                             "target= (a lowerable) or fn= (a raw body)")
        self.program = str(program)
        self.label = str(label if label is not None else program)
        self.target = target
        self.fn = fn
        self.jit_kw = dict(jit_kw or {})
        self.args = tuple(args)
        self.kwargs = dict(kwargs or {})

    def lower(self):
        if self.target is not None:
            return self.target.lower(*self.args, **self.kwargs)
        return jax.jit(self.fn, **self.jit_kw).lower(*self.args,
                                                     **self.kwargs)


class ContractClosure:
    """Static zero-retrace proof spec: ``points`` enumerates the
    workload's reachable dispatch points (every admissible serve batch
    size, every configured scan window, ...) and ``resolve(point)``
    returns the abstract argument tree that point would dispatch with —
    or None when the runtime provably rejects the point before the jit
    (serve admission refusing an over-bucket batch).  The verifier
    asserts every resolved signature is one of the declared cases'
    signatures; a miss is an unproven shape, rendered through the
    retrace explainer's diff."""

    __slots__ = ("points", "resolve")

    def __init__(self, points, resolve: Callable):
        self.points = list(points)
        self.resolve = resolve


class ProgramContract:
    """Declared invariants of one program family.

    ``build()`` is LAZY — declaring a contract at import time costs a
    dict insert; only the verifier (``python -m tools.mxlint
    --contracts``) ever builds the cases.  ``donate_argnums`` is the
    EXPECTED donation set: the verifier proves each donated leaf
    actually appears in the lowered executable's input→output aliasing
    (a dropped donation doubles HBM on TPU while CPU runs clean).
    ``temp_budget_bytes`` caps the compiled ``memory_analysis`` temp
    allocation — the static HBM-creep gate."""

    __slots__ = ("name", "build", "donate_argnums", "temp_budget_bytes",
                 "closure", "description", "origin")

    def __init__(self, name: str, build: Callable,
                 donate_argnums: Tuple[int, ...] = (),
                 temp_budget_bytes: Optional[int] = None,
                 closure: Optional[ContractClosure] = None,
                 description: str = "",
                 origin: Optional[Tuple[str, int]] = None):
        self.name = str(name)
        self.build = build
        self.donate_argnums = tuple(sorted(int(i) for i in donate_argnums))
        self.temp_budget_bytes = None if temp_budget_bytes is None \
            else int(temp_budget_bytes)
        self.closure = closure
        self.description = str(description)
        # (file, line) of the declaring site — contract findings anchor
        # there, like any other mxlint diagnostic
        self.origin = origin

    def manifest_entry(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "donate_argnums": list(self.donate_argnums),
            "temp_budget_bytes": self.temp_budget_bytes,
            "closure_points": (
                None if self.closure is None
                else [str(p) for p in self.closure.points]
                if isinstance(self.closure, ContractClosure)
                # lazy closure: built (with its cases) only when the
                # verifier runs — the static manifest records that one
                # exists without paying the build
                else "deferred"),
            "description": self.description,
        }


_contracts_lock = threading.Lock()
_contracts: Dict[str, ProgramContract] = {}


def declare_contract(name: str, build: Callable, *,
                     donate_argnums: Tuple[int, ...] = (),
                     temp_budget_bytes: Optional[int] = None,
                     closure: Optional[ContractClosure] = None,
                     description: str = "") -> ProgramContract:
    """Declare the contract for one program family.  ``build`` returns
    the :class:`ContractCase` list when the verifier runs; everything
    else is metadata recorded now.  Redeclaring a name replaces the
    entry (module reloads in tests)."""
    import sys as _sys
    frame = _sys._getframe(1)
    origin = (frame.f_code.co_filename, frame.f_lineno)
    c = ProgramContract(name, build, donate_argnums=donate_argnums,
                        temp_budget_bytes=temp_budget_bytes,
                        closure=closure, description=description,
                        origin=origin)
    with _contracts_lock:
        _contracts[c.name] = c
    return c


def contracts() -> List[ProgramContract]:
    with _contracts_lock:
        return [_contracts[k] for k in sorted(_contracts)]


def contract_manifest() -> Dict[str, Any]:
    """The declared (not built) manifest — what ships in
    tools/mxlint/contracts.json and what ``python -m tools.mxlint
    --check-manifest`` validates."""
    return {"schema": CONTRACT_SCHEMA,
            "contracts": [c.manifest_entry() for c in contracts()]}


def reset_contracts() -> None:
    with _contracts_lock:
        _contracts.clear()


# ---------------------------------------------------------------------------
# Device-buffer census
# ---------------------------------------------------------------------------

# claim priority, most specific first: a Servable's version arrays are
# the same buffers its source block's Parameters hold — the serving
# owner wins so a deployed version's footprint is visible as such.
# kv_cache (ISSUE 15) holds the decode engine's device-resident KV
# pool + per-slot token/length state, donated across decode steps —
# the bucket whose bytes must stay FLAT across generations.
# kv_pages (ISSUE 18) is the PAGED decode engine's shared page heap +
# block-table state — same flatness contract as kv_cache, but the
# bucket is sized in pages, not slots, so admission headroom reads off
# it directly.
CENSUS_OWNERS = ("serve", "kv_cache", "kv_pages", "ef_residuals",
                 "optimizer_state", "params")

_owners_lock = threading.Lock()
# obj -> (kind, extractor(obj) -> iterable of arrays/NDArrays)
_owners: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def track_buffers(kind: str, obj, extract: Callable) -> None:
    """Register `obj` as a buffer owner for the census.  `extract(obj)`
    yields its current device arrays (jax arrays or NDArray-likes) when
    the census runs; held weakly, so owners never leak through the
    census itself."""
    try:
        with _owners_lock:
            _owners[obj] = (str(kind), extract)
    except TypeError:
        pass            # not weakref-able: stay uncounted ("other")


def _owned_ids() -> Dict[str, set]:
    with _owners_lock:
        items = list(_owners.items())
    by_kind: Dict[str, set] = {k: set() for k in CENSUS_OWNERS}
    for obj, (kind, extract) in items:
        ids = by_kind.setdefault(kind, set())
        try:
            arrays = extract(obj)
        except Exception:
            continue
        for a in arrays or ():
            a = getattr(a, "_jax", a)
            if a is not None:
                ids.add(id(a))
    return by_kind


def _per_chip_nbytes(a, nbytes: int) -> int:
    """Bytes ONE chip holds of array `a` (ISSUE 14): the shard extent
    under the array's sharding — full bytes when replicated or
    single-device, ``nbytes / prod(sharded axes)`` when sheet/tensor
    sharded.  This is the number that must drop ~linearly with the fsdp
    axis for params + optimizer state."""
    try:
        sh = getattr(a, "sharding", None)
        if sh is None:
            return nbytes
        shape = tuple(a.shape)
        shard_shape = sh.shard_shape(shape)
        full = 1
        part = 1
        for d in shape:
            full *= int(d)
        for d in shard_shape:
            part *= int(d)
        if full <= 0:
            return nbytes
        return (nbytes * part) // full
    except Exception:
        return nbytes


def buffer_census() -> Dict[str, Any]:
    """Bucket every live device array by owner.

    Walks ``jax.live_arrays()`` host-side (array handles + nbytes
    metadata — no device sync, no transfer) and attributes each to the
    first owner bucket claiming its id; unclaimed arrays land in
    ``other`` (activations in flight, test droppings, leaks).  Each
    bucket reports global ``bytes`` and sharding-aware
    ``bytes_per_chip`` (the per-device footprint: a mesh-sharded param's
    shard extent, the full value when replicated) — the acceptance
    series for the FSDP lane."""
    by_kind = _owned_ids()
    order = [k for k in CENSUS_OWNERS if k in by_kind] + \
        [k for k in by_kind if k not in CENSUS_OWNERS]
    out: Dict[str, Any] = {k: {"count": 0, "bytes": 0,
                               "bytes_per_chip": 0}
                           for k in order + ["other"]}
    total = 0
    total_chip = 0
    n = 0
    try:
        live = jax.live_arrays()
    except Exception:
        live = []
    for a in live:
        try:
            if getattr(a, "is_deleted", lambda: False)():
                continue
            nbytes = int(a.nbytes)
        except Exception:
            continue
        chip_bytes = _per_chip_nbytes(a, nbytes)
        aid = id(a)
        for kind in order:
            if aid in by_kind[kind]:
                slot = out[kind]
                break
        else:
            slot = out["other"]
        slot["count"] += 1
        slot["bytes"] += nbytes
        slot["bytes_per_chip"] += chip_bytes
        total += nbytes
        total_chip += chip_bytes
        n += 1
    out["total_bytes"] = total
    out["total_bytes_per_chip"] = total_chip
    out["n_arrays"] = n
    return out


class LeakDetector:
    """Step-over-step live-byte growth detector.

    Each :meth:`check` snapshots the census, publishes per-owner
    ``census_live_bytes{owner}`` gauges, and accumulates consecutive
    total growth; when the streak exceeds ``MX_LEAK_WARN_BYTES`` the
    ``census_leak_bytes`` gauge latches the streak size,
    ``census.leak_trips`` increments and a warning names the growing
    buckets.  Any shrink resets the streak (steady-state training
    reuses buffers; a true leak only ever grows)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._prev_total: Optional[int] = None
        self._prev_census: Optional[Dict[str, Any]] = None
        self._growth = 0
        self._tripped = False
        reg = _telemetry.registry
        self._g_leak = reg.gauge(
            "census_leak_bytes",
            doc="consecutive step-over-step live-byte growth "
                "(0 until it exceeds MX_LEAK_WARN_BYTES)")
        self._c_trips = reg.counter(
            "census.leak_trips",
            doc="times the buffer-census leak detector tripped")

    def reset(self) -> None:
        with self._lock:
            self._prev_total = None
            self._prev_census = None
            self._growth = 0
            self._tripped = False
        self._g_leak.set(0)

    def check(self) -> Dict[str, Any]:
        census = buffer_census()
        reg = _telemetry.registry
        for kind, slot in census.items():
            if isinstance(slot, dict):
                reg.gauge("census_live_bytes",
                          doc="live device bytes by owner bucket",
                          labels={"owner": kind}).set(slot["bytes"])
        try:
            warn_bytes = int(get_env("MX_LEAK_WARN_BYTES", 64 << 20, int)
                             or 0)
        except (TypeError, ValueError):
            warn_bytes = 64 << 20
        total = census["total_bytes"]
        growers = []
        with self._lock:
            if self._prev_total is not None:
                delta = total - self._prev_total
                if delta > 0:
                    self._growth += delta
                    prev = self._prev_census or {}
                    for kind, slot in census.items():
                        if not isinstance(slot, dict):
                            continue
                        before = (prev.get(kind) or {}).get("bytes", 0)
                        if slot["bytes"] > before:
                            growers.append(
                                (kind, slot["bytes"] - before))
                elif delta < 0:
                    # only a SHRINK resets the streak — a flat plateau
                    # between growth steps (allocator reuse) must not
                    # hide a monotonically growing leak
                    self._growth = 0
                    self._tripped = False
            self._prev_total = total
            self._prev_census = census
            growth = self._growth
            tripped = warn_bytes > 0 and growth >= warn_bytes
            first_trip = tripped and not self._tripped
            self._tripped = tripped
        self._g_leak.set(growth if tripped else 0)
        if first_trip:
            self._c_trips.inc()
            logger.warning(
                "buffer-census leak suspect: live bytes grew %d over "
                "consecutive checks (MX_LEAK_WARN_BYTES=%d); growing "
                "buckets this check: %s; census: %s",
                growth, warn_bytes,
                ", ".join("%s+%d" % g for g in growers) or "other",
                {k: v for k, v in census.items() if isinstance(v, dict)})
        return {"census": census, "growth_bytes": growth,
                "tripped": tripped}


leak_detector = LeakDetector()

# Flight-recorder wiring: every Nth step record carries the census
# totals + leak streak (cheap enough to ride along; a live_arrays walk
# per step would not be).
_CENSUS_EVERY = 16
_census_tick = [0]
_census_tick_lock = threading.Lock()


def _step_census_observer() -> Optional[Dict[str, Any]]:
    if not census_enabled():
        return None
    with _census_tick_lock:
        _census_tick[0] += 1
        due = _census_tick[0] % _CENSUS_EVERY == 1
    if not due:
        return None
    chk = leak_detector.check()
    return {"live_bytes": chk["census"]["total_bytes"],
            "leak_bytes": chk["growth_bytes"]}


def _crash_census() -> Dict[str, Any]:
    return buffer_census()


_telemetry.register_step_observer(_step_census_observer)
_telemetry.register_crash_section("buffer_census", _crash_census)
_telemetry.register_crash_section("programs", program_table)
