"""Serving RPC front: PREDICT / GENERATE / HEALTH / SWAP / STOP over
the kvstore wire.

Transport and envelope are the kvstore server's, verbatim: length-
prefixed pickles (``kvstore.server.send_msg/recv_msg``), requests
optionally wrapped ``("SEQ", client_id, seq, inner[, (trace_id,
span_id)])`` with an exactly-once replay cache — a client that
reconnects after a dropped reply replays the same seq and is answered
from the cache instead of re-executing (a replayed PREDICT must not
burn a second dispatch; a replayed SWAP must not double-bump the
version).  Tensors cross as numpy-only ``NPX`` tuples
(``kvstore.wire_codec.encode_array``), so the wire never carries a
device array and health tools never import the kernel stack.

Verbs::

  PREDICT  (PREDICT, [npx, ...])          -> (True, (version, [npx, ...]))
  GENERATE (GENERATE, [tok, ...], opts)   -> (True, (version, [tok, ...]))
           autoregressive decode through the continuous-batching engine
           (ISSUE 15); opts = {"max_tokens": N, "stream": bool,
           "eos": tok} (eos = per-request stop token).  With
           stream=True the terminal reply is preceded by zero or more
           ("STREAM", offset, [tok, ...]) frames as tokens are
           harvested — chunks are at-least-once (a failover replays
           from offset 0; the offset lets the client dedupe), the
           terminal (version, tokens) reply is exactly-once via the
           replay cache like PREDICT: a replayed COMPLETED sequence is
           answered from the cache, never re-generated.
  HEALTH   (HEALTH,)                      -> (True, {status, version, ...})
  METRICS  (METRICS[, fmt])               -> (True, (TXT, utf8-bytes)):
           the live Prometheus text exposition (fmt='json': the JSON
           registry snapshot) — a replica is scrapeable with no sidecar
  SWAP     (SWAP, prefix, epoch, inputs)  -> (True, new_version)
  DRAIN    (DRAIN[, timeout])             -> (True, {status, ...}):
           first-class retirement (ISSUE 17) — stop ADMITTING new work
           (fresh PREDICT/GENERATE get ``(False, "draining: ...")``),
           let in-flight requests and generations finish, then exit the
           serve loop cleanly.  Past the bounded drain deadline
           (``timeout`` or MX_SERVE_DRAIN_TIMEOUT) the stragglers'
           connections are severed with NO reply, so their clients fail
           over and re-prefill on a survivor — exactly the
           mid-generation-kill story, but only for the stragglers.
  STOP     (STOP,)                        -> (True, "stopping")

Overload is a NORMAL reply — ``(False, "overloaded: ...")`` — so the
client can distinguish load shedding (report/back off; the replica is
healthy) from a dead replica (fail over).  A DRAINING replica refuses
new work the same way (``(False, "draining: ...")``): the
router/client route the request to another replica instead of burning
a retry deadline here.

Tracing: the handler opens ``serve.server.<CMD>`` as a child of the
client's wire-propagated span, and hands its own (trace_id, span_id) to
the batcher with the request, so the batch's ``serve_dispatch`` span
events close the client → server → batcher → dispatch chain.

Chaos: every request passes the ``serve.request`` fault site —
``tools/launch.py --fault 'serve.request:crash:after=N'`` kills the
replica mid-load exactly like the worker-fit chaos lane, which is how
tools/chaos_smoke.sh proves failover + supervisor restart.
"""
from __future__ import annotations

import os
import socket
import socketserver
import threading
from typing import Dict, Optional, Sequence

from ..base import MXNetError, get_env
from .. import fault as _fault
from .. import telemetry as _telemetry
from ..kvstore.server import send_msg, recv_msg
from ..kvstore.wire_codec import (WireCodecError, decode_array,
                                  encode_array, encode_text)
from ..kvstore.wire_verbs import declare_verbs
from .batcher import Batcher, Overloaded, result_timeout
from .servable import BudgetExceeded, ModelHost, Servable

__all__ = ["ServeServer", "serve_forever"]

# The serving wire surface, DECLARED (ISSUE 11): mxlint's
# wire-verb-exhaustive rule pairs every ServeClient-emitted verb with
# an entry here, checks this file handles it, that 'replayable' verbs
# sit in the exactly-once replay set (_CACHED) and 'idempotent' ones do
# not, and that named codecs have encode_*/decode_* pairs in
# kvstore/wire_codec.py.  The serve router (ISSUE 17) speaks this SAME
# surface — it forwards client envelopes verbatim, so its manifest in
# router.py mirrors these rows and the replay semantics hold
# end-to-end through it.
WIRE_VERBS = declare_verbs("serve", {
    # one PREDICT = one dispatch, even replayed; one SWAP = one flip
    "PREDICT": {"semantics": "replayable", "replay": "cached",
                "codec": "array", "mutates": ("engine",)},
    "SWAP": {"semantics": "replayable", "replay": "cached",
             "codec": None, "mutates": ("model",)},
    # one GENERATE = one generated sequence: a replayed COMPLETED
    # sequence answers from the cache (tokens are plain int lists — no
    # tensor codec); fresh streaming runs emit STREAM frames ahead of
    # the terminal reply
    "GENERATE": {"semantics": "replayable", "replay": "cached",
                 "codec": None, "mutates": ("engine",),
                 "stream": "STREAM"},
    # STREAM is the server->client token-chunk frame of a streaming
    # GENERATE, not a request verb: a client SENDING it is answered
    # with an explicit error (see handle()), and chunks re-emitted
    # after a failover dedupe by offset — re-delivery is harmless
    "STREAM": {"semantics": "idempotent", "replay": "bypass",
               "codec": None, "mutates": ()},
    # probes and shutdown re-execute harmlessly on a retried envelope
    "HEALTH": {"semantics": "idempotent", "replay": "bypass",
               "codec": None, "mutates": ()},
    "METRICS": {"semantics": "idempotent", "replay": "bypass",
                "codec": "text", "mutates": ()},
    "STOP": {"semantics": "idempotent", "replay": "bypass",
             "codec": None, "mutates": ()},
    # drain-not-kill retirement (ISSUE 17): re-asserting an already-
    # draining replica is a no-op, so a retried DRAIN is harmless
    "DRAIN": {"semantics": "idempotent", "replay": "bypass",
              "codec": None, "mutates": ("lifecycle",)},
}, role="server", durable=False, handler="ServeServer.handle")


class ServeServer:
    """Verb handlers + replay cache over one (ModelHost, Batcher) pair,
    plus an optional continuous-batching decode engine (``decode=``, a
    :class:`~mxnet_tpu.serve.decode.DecodeBatcher`) behind the GENERATE
    verb."""

    # replies worth exactly-once semantics; HEALTH re-executes harmlessly
    _CACHED = ("PREDICT", "SWAP", "GENERATE")

    def __init__(self, host: Optional[ModelHost] = None,
                 batcher: Optional[Batcher] = None, decode=None,
                 **batcher_kw):
        self.host = host or ModelHost()
        self.batcher = batcher or Batcher(self.host, **batcher_kw)
        self.decode = decode
        # co-hosted decode engines join the host's engine map so the
        # budget packer counts their models (a speculative pair's
        # draft + target) and FLEET/HEALTH can enumerate them; never
        # mutated from a verb branch
        if decode is not None:
            self.host.engines.setdefault(decode.servable.name, decode)
        # client_id -> [seq, done Event, resp]  (same shape as the
        # kvstore server's cache; one in-flight entry per client).
        # Serving clients are ephemeral (every ServeClient is a fresh
        # uuid), unlike the kvstore's fixed worker population — without
        # eviction each dead client's last PREDICT response (a full
        # output tensor) would be retained forever.  Bounded per-client
        # LRU: dict insertion order IS recency order because every
        # touch (new seq or replay hit) moves the entry to the end;
        # over-cap inserts evict the least-recently-touched RESOLVED
        # entries, counted in serve.replay_evicted.
        try:
            raw_cap = get_env("MX_SERVE_REPLAY_CAP", 512, int)
            # values < 1 clamp to 1 (never silently back to the
            # default): the exactly-once contract requires at least the
            # in-flight entry, so 0 cannot mean "disabled"
            self._replay_cap = max(1, int(512 if raw_cap is None
                                          else raw_cap))
        except (TypeError, ValueError):
            self._replay_cap = 512
        self._replay: Dict[str, list] = {}
        self._replay_lock = threading.Lock()
        self._c_evicted = _telemetry.registry.counter(
            "serve.replay_evicted",
            doc="replay-cache entries dropped by the per-client LRU "
                "bound (MX_SERVE_REPLAY_CAP)")
        # drain-not-kill retirement (ISSUE 17): once set, admission is
        # closed (fresh PREDICT/GENERATE refused with "draining: ...")
        # while in-flight work finishes against the bounded deadline
        self._draining = threading.Event()
        self._drain_lock = threading.Lock()
        self._drain_deadline: Optional[_fault.Deadline] = None

    # -- multi-model lifecycle (ISSUE 20; startup/admin path, NOT a
    # verb branch — engines are never created inside handle()) --------------
    def add_model(self, servable: Servable, example=None,
                  **batcher_kw) -> Servable:
        """Deploy one more named model onto this replica: warm + budget
        admission through ``ModelHost.deploy`` (raises
        :class:`BudgetExceeded` on a bust, nothing retained), then give
        the non-default model its own micro-batcher in
        ``host.engines`` so PREDICTs carrying its name coalesce
        independently of the default lane."""
        sv = self.host.deploy(servable, example=example)
        if sv.name != self.host.default_model and \
                sv.name not in self.host.engines:
            self.host.engines[sv.name] = Batcher(
                self.host, model=sv.name, **batcher_kw)
        return sv

    # -- envelope (kvstore SEQ contract) ------------------------------------
    def handle_request(self, msg, stream_fn=None):
        """``stream_fn(offset, tokens)`` — provided by the socket
        handler — emits one ("STREAM", offset, tokens) frame ahead of
        the terminal reply; only a FRESH streaming GENERATE uses it
        (replays answer terminally from the cache)."""
        if isinstance(msg, tuple) and msg and msg[0] == "SEQ":
            cid, seq, inner = msg[1], msg[2], msg[3]
            tctx = msg[4] if len(msg) > 4 else None
            cmd = inner[0] if inner else None
            with _telemetry.rpc_span(
                    "serve.server.%s" % cmd,
                    trace_id=tctx[0] if tctx else None,
                    parent_id=tctx[1] if tctx else None) as span:
                return self._handle_seq(cid, seq, inner, cmd, span,
                                        stream_fn=stream_fn)
        return self.handle(msg, stream_fn=stream_fn)

    def _handle_seq(self, cid, seq, inner, cmd, span, stream_fn=None):
        if cmd not in self._CACHED:
            return self.handle(inner, span=span)
        with self._replay_lock:
            ent = self._replay.get(cid)
            if ent is not None and seq == ent[0]:
                dup = ent
                # LRU touch: a replaying client is alive — move it to
                # the recent end so churn from new clients cannot evict
                # its in-flight exactly-once entry
                self._replay[cid] = self._replay.pop(cid)
            elif ent is not None and seq < ent[0]:
                span.event("stale", seq=seq, server_at=ent[0])
                return False, ("stale request seq %s (server already at "
                               "%s)" % (seq, ent[0]))
            else:
                dup = None
                ent = [seq, threading.Event(), None]
                self._replay.pop(cid, None)   # re-insert at recent end
                self._replay[cid] = ent
                if len(self._replay) > self._replay_cap:
                    self._evict_replay_locked()
        if dup is not None:
            span.event("replay", seq=seq)
            _telemetry.registry.counter(
                "serve.server_replays",
                doc="PREDICT/SWAP requests answered from the "
                    "exactly-once replay cache").inc()
            timeout = (get_env("MX_SERVE_TIMEOUT", 30.0, float) or 30.0) + 5
            if not dup[1].wait(timeout=timeout):
                return False, "replayed request %s still in flight" % seq
            return dup[2]
        try:
            resp = self.handle(inner, span=span, stream_fn=stream_fn)
        except BaseException as e:
            ent[2] = (False, "serve error handling %r: %s" % (cmd, e))
            ent[1].set()
            raise
        ent[2] = resp
        ent[1].set()
        return resp

    def _evict_replay_locked(self) -> None:
        """Caller holds _replay_lock.  Drop least-recently-touched
        RESOLVED entries until back under the cap; in-flight entries
        (Event not set) are never evicted — their replay semantics are
        live.  Each eviction bumps serve.replay_evicted."""
        evicted = 0
        for cid in list(self._replay):
            if len(self._replay) <= self._replay_cap:
                break
            ent = self._replay[cid]
            if ent[1].is_set():
                del self._replay[cid]
                evicted += 1
        if evicted:
            self._c_evicted.inc(evicted)

    # -- verbs --------------------------------------------------------------
    def handle(self, msg, span=None, stream_fn=None):
        cmd = msg[0]
        if cmd == "PREDICT":
            # optional third element: the target model's name on a
            # multi-model replica (absent/None -> the default model)
            return self._predict(msg[1], span,
                                 model=msg[2] if len(msg) > 2 else None)
        if cmd == "GENERATE":
            opts = msg[2] if len(msg) > 2 else {}
            return self._generate(msg[1], opts or {}, span, stream_fn)
        if cmd == "STREAM":
            # server->client frame only; a client emitting it as a
            # request is a protocol error, answered explicitly
            return False, ("STREAM is a server-to-client token frame, "
                           "not a request verb")
        if cmd == "HEALTH":
            return True, self.health()
        if cmd == "METRICS":
            # live Prometheus scrape over the serve wire (ISSUE 10
            # satellite): no sidecar needed — the reply is the whole
            # instrument registry (serve.* counters, program census,
            # phase histograms) as one TXT payload
            fmt = msg[1] if len(msg) > 1 else "prometheus"
            reg = _telemetry.registry
            # a scrape self-describes the replica (ISSUE 12): the active
            # servable rides the exposition as a model-labeled version
            # gauge, which is where the fleet collector/federation get
            # their `model` label from (no extra HEALTH round-trip)
            for name in self.host.models():
                try:
                    sv = self.host.active(name)
                except MXNetError:
                    continue    # raced an empty host / retired model
                reg.gauge("serve.active_version",
                          doc="live servable version per hosted model",
                          labels={"model": sv.name}).set(sv.version)
            text = reg.to_json(indent=1) if fmt == "json" \
                else reg.to_prometheus()
            return True, encode_text(text)
        if cmd == "SWAP":
            _, prefix, epoch, input_names = msg
            try:
                version = self.swap(prefix, epoch, input_names)
            except BudgetExceeded as e:
                # typed in-band refusal (ISSUE 20): the packer said no —
                # the replica is healthy, the model just does not fit
                # under MX_SERVE_HBM_BUDGET; nothing was retained
                return False, "budget: %s" % e
            except Exception as e:      # incl. a broken model's trace
                # error: the old version stays live, the caller gets
                # the reason instead of a severed connection
                return False, "swap failed: %s" % e
            return True, version
        if cmd == "DRAIN":
            timeout = msg[1] if len(msg) > 1 else None
            return True, self.drain(timeout)
        if cmd == "STOP":
            return True, "stopping"
        return False, "unknown serve command %r" % (cmd,)

    # -- drain lifecycle (ISSUE 17) -----------------------------------------
    def drain(self, timeout=None) -> Dict:
        """Begin retirement: close admission, arm the bounded drain
        deadline (idempotent — a re-asserted DRAIN keeps the FIRST
        deadline so a retry cannot extend the retirement window), and
        report what is still in flight.  ``serve_forever`` watches
        :meth:`drain_idle` / :meth:`drain_expired` and exits the serve
        loop when the replica is empty or the deadline passes."""
        t = float(timeout if timeout is not None else
                  get_env("MX_SERVE_DRAIN_TIMEOUT", 30.0, float) or 30.0)
        with self._drain_lock:
            if self._drain_deadline is None:
                self._drain_deadline = _fault.Deadline(t)
            self._draining.set()
            remaining = self._drain_deadline.remaining()
        _telemetry.registry.counter(
            "serve.drains",
            doc="DRAIN retirements accepted by this replica").inc()
        status = {"status": "draining",
                  "deadline_seconds": remaining,
                  "queue_rows": self.batcher.queue_rows()}
        if self.decode is not None:
            status["active"] = self.decode.active_count()
            status["queued"] = self.decode.queue_depth()
        return status

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def drain_idle(self) -> bool:
        """True when nothing is left in flight inside the engines (the
        wire-level in-flight count is ``serve_forever``'s half)."""
        if self.batcher.queue_rows() > 0:
            return False
        if self.decode is not None and (
                self.decode.active_count() > 0
                or self.decode.queue_depth() > 0):
            return False
        return True

    def drain_expired(self) -> bool:
        with self._drain_lock:
            dl = self._drain_deadline
        return dl is not None and dl.expired()

    def _predict(self, payload: Sequence, span, model=None):
        if self._draining.is_set():
            # admission is closed: a NORMAL reply (not a severed
            # socket) so the router/client re-routes instead of
            # replaying a poison request against a retiring replica
            return False, ("draining: replica is retiring, not "
                           "admitting new work")
        try:
            arrays = [decode_array(t) for t in payload]
        except ValueError as e:
            return False, "bad PREDICT payload: %s" % e
        tctx = span.wire_context() if span is not None else None
        # model routing (ISSUE 20): a named non-default model rides its
        # own micro-batcher (host.engines, created at deploy, read-only
        # here); no/None/default name keeps the single-model fast path
        eng = self.batcher
        if model is not None and model != self.host.default_model:
            eng = self.host.engines.get(model)
            if not isinstance(eng, Batcher):
                return False, ("unknown model %r (hosted: %s)"
                               % (model,
                                  ", ".join(self.host.models()) or
                                  "none"))
        try:
            pending = eng.submit(arrays, trace_ctx=tctx) \
                if eng is not self.batcher \
                else self.batcher.submit(arrays, trace_ctx=tctx)
        except Overloaded as e:
            return False, "overloaded: %s" % e
        except MXNetError as e:
            return False, str(e)
        # server-side wait stays INSIDE the client's recv window (which
        # started earlier and includes network time), so a backlogged
        # replica sheds with an explicit reply instead of the client
        # timing out first and mistaking it for a dead replica
        timeout = max(1.0, result_timeout(None) - 2.0)
        try:
            version, outs = pending.result(timeout=timeout)
        except Exception as e:
            # ANY dispatch failure (XLA runtime error, OOM, a broken
            # foreign model's forward) must come back as a normal
            # (False, reason) reply — a severed connection would make
            # the client replay the poison request on every replica
            return False, "predict failed: %s: %s" % (type(e).__name__, e)
        return True, (version, [encode_array(o) for o in outs])

    def _generate(self, prompt, opts, span, stream_fn):
        """GENERATE: submit into the continuous-batching decode engine,
        optionally stream token chunks, answer the complete sequence.
        Like PREDICT, every failure is a normal (False, reason) reply —
        a severed connection would make the client replay a poison
        request on every replica."""
        if self._draining.is_set():
            # new generations (even from a session pinned here) are new
            # WORK: refuse so the router re-pins the session elsewhere;
            # generations already inside the pump keep running
            return False, ("draining: replica is retiring, not "
                           "admitting new sessions")
        if self.decode is None:
            return False, ("no decode engine deployed (start the "
                           "replica with --decode)")
        try:
            prompt = [int(t) for t in prompt]
        except (TypeError, ValueError):
            return False, "bad GENERATE payload: prompt must be token ids"
        tctx = span.wire_context() if span is not None else None
        max_new = opts.get("max_tokens")
        # model routing (ISSUE 20): the envelope may name which hosted
        # LM to decode with; the default engine answers unnamed (and
        # its own name), other names resolve through host.engines
        model = opts.get("model")
        eng = self.decode
        if model is not None and model != self.decode.servable.name:
            cand = self.host.engines.get(model)
            if cand is None or isinstance(cand, Batcher) or \
                    not hasattr(cand, "submit"):
                return False, ("unknown model %r (decode engines: %s)"
                               % (model, self.decode.servable.name))
            eng = cand
        try:
            pending = eng.submit(prompt, max_new=max_new,
                                 eos_id=opts.get("eos"),
                                 trace_ctx=tctx) \
                if eng is not self.decode \
                else self.decode.submit(prompt, max_new=max_new,
                                        eos_id=opts.get("eos"),
                                        trace_ctx=tctx)
        except Overloaded as e:
            return False, "overloaded: %s" % e
        except MXNetError as e:
            return False, str(e)
        # like PREDICT: stay inside the client's recv window so a slow
        # generation sheds with an explicit reply, not a dead socket
        timeout = max(1.0, result_timeout(None) - 2.0)
        deadline = _fault.Deadline(timeout)
        try:
            if opts.get("stream") and stream_fn is not None:
                sent = 0
                while not deadline.expired():
                    chunk, done = pending.wait_new(sent, timeout=0.25)
                    if chunk:
                        stream_fn(sent, [int(t) for t in chunk])
                        sent += len(chunk)
                    if done:
                        break
            tokens = pending.result(timeout=max(0.001,
                                                deadline.remaining()))
        except Exception as e:
            return False, "generate failed: %s: %s" % (type(e).__name__,
                                                       e)
        return True, (eng.version, [int(t) for t in tokens])

    def health(self) -> Dict:
        reg = _telemetry.registry
        try:
            sv = self.host.active()
            status: Dict = {"status": "serving", "version": sv.version,
                            "model": sv.name,
                            "buckets": list(sv.buckets.sizes),
                            "param_platform": sv.param_platform(),
                            "retraces": sv.retraces,
                            "bucket_hits": sv.bucket_hits}
        except MXNetError:
            status = {"status": "empty", "version": 0}
        # multi-model packing (ISSUE 20): per-model versions/footprints
        # against the HBM budget, so the fleet can see what this
        # replica co-hosts and how much headroom it has left
        models = self.host.models()
        if len(models) > 1 or self.host.hbm_budget > 0 or \
                self.host.engines:
            status["packing"] = self.host.packing_report()
        if self.decode is not None:
            # a decode-only replica is serving even with an empty host
            dsv = self.decode.servable
            status["status"] = "serving"
            status["decode"] = {
                "model": dsv.name, "version": dsv.version,
                "engine": getattr(dsv, "engine", "flat"),
                "param_platform": dsv.param_platform(),
                "slots": dsv.config.slots,
                "active": self.decode.active_count(),
                "queued": self.decode.queue_depth(),
                "slot_buckets": list(dsv.config.slot_buckets),
                "prompt_buckets": list(dsv.config.prompt_buckets),
                "retraces": dsv.retraces,
                "tokens": reg.value("serve.decode.tokens"),
                "sequences": reg.value("serve.decode.sequences"),
            }
            # paged engine (ISSUE 18): page-level admission headroom +
            # prefix-sharing savings ride the same health dict
            page_stats = self.decode.page_stats()
            if page_stats is not None:
                status["decode"].update(page_stats)
        if self._draining.is_set():
            # a draining replica still ANSWERS (in-flight work, probes)
            # but must advertise that it admits nothing new
            status["status"] = "draining"
        status.update({
            "queue_rows": self.batcher.queue_rows(),
            "requests": reg.value("serve.requests"),
            "rejected": reg.value("serve.rejected"),
            "batches": reg.value("serve.batches"),
            "pid": os.getpid(),
        })
        return status

    def swap(self, prefix: str, epoch: int,
             input_names: Sequence[str]) -> int:
        """Load ``prefix`` as version N+1, warm it with the active
        version's signature, flip, drain — the wire face of
        ``ModelHost.deploy``."""
        new_version = self.host.version + 1
        kw = {}
        cur_name = self.host.default_model
        if cur_name is not None:
            # a SWAP replaces the DEFAULT model's version chain — same
            # name, next version — not a new co-hosted model (add_model
            # is the multi-model admission path) — on the same device
            # (the handler thread has no default context of its own)
            kw["name"] = cur_name
            kw["ctx"] = self.host.active().ctx
        sv = Servable.from_checkpoint(prefix, epoch=epoch,
                                     input_names=input_names,
                                     version=new_version, **kw)
        example = None
        try:
            want = self.host.active().warmed_signature
            if want is not None:
                import numpy as _np
                example = [_np.zeros((1,) + trail, dtype=dt)
                           for trail, dt in want]
        except MXNetError:
            pass
        self.host.deploy(sv, example=example)
        return new_version

    def close(self) -> None:
        self.batcher.close()
        if self.decode is not None:
            self.decode.close()


def serve_forever(port: Optional[int] = None,
                  state: Optional[ServeServer] = None,
                  ready_file: Optional[str] = None,
                  stop_event: Optional[threading.Event] = None,
                  abort_event: Optional[threading.Event] = None) -> None:
    """Run one serving replica's accept loop (modeled on
    ``kvstore.server.serve_forever``: threaded handlers, graceful STOP
    drain, surviving connections severed on the way out).

    ``abort_event`` is the chaos hook for in-process tests: setting it
    severs the listener and every live connection IMMEDIATELY — no
    drain, no replies — which is what a killed replica looks like to
    its clients (the subprocess lane uses the ``serve.request`` crash
    fault instead).
    """
    port = int(port if port is not None else get_env("MX_SERVE_PORT"))
    server_state = state or ServeServer()
    stop_event = stop_event or threading.Event()
    abort_event = abort_event or threading.Event()
    inflight_count = [0]
    inflight_lock = threading.Lock()
    conns = set()
    conns_lock = threading.Lock()

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            with conns_lock:
                conns.add(self.request)
            try:
                self._serve()
            finally:
                with conns_lock:
                    conns.discard(self.request)

        def _serve(self):
            while not abort_event.is_set():
                try:
                    msg = recv_msg(self.request, idle_block=True)
                except (ConnectionError, OSError, TimeoutError):
                    return
                with inflight_lock:
                    inflight_count[0] += 1
                sock = self.request

                def stream_fn(offset, tokens):
                    # token chunks of a streaming GENERATE ride ahead
                    # of the terminal reply on the same connection
                    send_msg(sock, ("STREAM", offset, tokens))

                try:
                    _fault.fire("serve.request")
                    ok, payload = server_state.handle_request(
                        msg, stream_fn=stream_fn)
                except SystemExit:      # injected crash: die mid-request
                    os._exit(17)
                except (_fault.FaultError, WireCodecError) as e:
                    # malformed wire frame: decoders raise before any
                    # state is touched, so reply a typed refusal on the
                    # same connection instead of severing it
                    ok, payload = False, str(e)
                finally:
                    with inflight_lock:
                        inflight_count[0] -= 1
                try:
                    send_msg(self.request, (ok, payload))
                except (ConnectionError, OSError):
                    return
                inner = msg[3] if isinstance(msg, tuple) and msg and \
                    msg[0] == "SEQ" else msg
                if inner and inner[0] == "STOP":
                    stop_event.set()
                    return

    class Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    def _sever():
        with conns_lock:
            leftover = list(conns)
        for c in leftover:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass

    with Server(("0.0.0.0", port), Handler) as srv:
        if ready_file:
            with open(ready_file, "w") as f:
                f.write("%d" % srv.server_address[1])
        t = threading.Thread(target=srv.serve_forever, daemon=True,
                             name="mx-serve-accept")
        t.start()
        # idle until STOP (a replica's lifetime), a completed/expired
        # DRAIN retirement (ISSUE 17), or the chaos abort — the
        # supervisor owns killing an abandoned replica
        drain_overrun = False
        while not stop_event.is_set() and not abort_event.is_set():
            stop_event.wait(timeout=0.1)
            if server_state.draining:
                with inflight_lock:
                    wire_busy = inflight_count[0]
                if wire_busy == 0 and server_state.drain_idle():
                    break                   # drained clean: exit 0
                if server_state.drain_expired():
                    # bounded deadline passed with stragglers still in
                    # flight: sever them WITHOUT replies so their
                    # clients fail over and re-prefill on a survivor —
                    # the mid-generation-kill story, stragglers only
                    drain_overrun = True
                    break
        if drain_overrun:
            _sever()
            srv.shutdown()
            server_state.close()
            return
        if abort_event.is_set():
            # simulated crash: live connections die FIRST (no drain, no
            # replies — socketserver's shutdown() can block up to its
            # 0.5s poll interval, and a "killed" replica must not keep
            # answering in-flight requests through that window), then
            # the listener stops
            _sever()
            srv.shutdown()
            server_state.close()
            return
        srv.shutdown()                      # stop accepting
        drain_deadline = _fault.Deadline(5.0)
        while not drain_deadline.expired():
            with inflight_lock:
                if inflight_count[0] == 0:
                    break
            _fault.sleep(0.02)
        server_state.close()
        _sever()
