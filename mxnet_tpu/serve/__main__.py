"""``python -m mxnet_tpu.serve`` — run one serving replica.

The process face of the serving engine: load a servable (an exported /
foreign ``<prefix>-symbol.json`` + ``.params`` checkpoint, or the
built-in deterministic demo model), AOT-warm every batch bucket, then
serve PREDICT/HEALTH/SWAP on a TCP port until a STOP arrives.

Multi-replica serving rides ``tools/launch.py``: with ``--port-base P``
each supervised rank binds ``P + MX_PROCESS_ID``, and when the launcher
provisions ``MX_HEARTBEAT_FILE`` the batcher loop beats it (throttled)
so ``--hang-timeout`` health-gates restarts — a wedged replica is
killed and respawned with its original env, a crashed one (e.g. the
``serve.request`` chaos fault) restarts and warms back up while clients
fail over to the survivors.

Examples::

  python -m mxnet_tpu.serve --demo --port 9700
  python -m mxnet_tpu.serve --decode --port 9700     # GENERATE lane
  python tools/launch.py -n 2 --restart on-failure -- \\
      python -m mxnet_tpu.serve --demo --port-base 9700
  python -m mxnet_tpu.serve --model /ckpt/resnet --epoch 3 \\
      --inputs data --example-shape 3,224,224
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time

import numpy as _np


def _build_servables(args):
    """Every --demo/--model spec as (servable, example) —
    multi-model co-hosting (ISSUE 20): the FIRST spec is the default
    model, the rest are admitted through ``ServeServer.add_model``
    under the MX_SERVE_HBM_BUDGET packer and addressed by the wire
    envelope's model field.  Parameters load onto the current context
    (main() builds under ``tpu(0)``)."""
    from .servable import BucketTable, Servable
    buckets = BucketTable([int(b) for b in args.buckets.split(",")]) \
        if args.buckets else None
    specs = []
    if args.demo:
        from .demo import demo_block, demo_example
        specs.append((Servable(demo_block(), name="demo-mlp",
                               version=1, buckets=buckets),
                      demo_example()))
    for prefix in (args.model or ()):
        sv = Servable.from_checkpoint(prefix, epoch=args.epoch,
                                      input_names=args.inputs.split(","),
                                      version=1, buckets=buckets)
        if not args.example_shape:
            raise SystemExit("serve: --model needs --example-shape "
                             "(comma dims per input, ';' between "
                             "inputs)")
        example = []
        for part in args.example_shape.split(";"):
            trail = tuple(int(d) for d in part.split(",") if d.strip())
            example.append(_np.zeros((1,) + trail,
                                     _np.dtype(args.dtype)))
        specs.append((sv, example))
    return specs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.serve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model", action="append", default=None,
                    metavar="PREFIX",
                    help="checkpoint prefix (PREFIX-symbol.json + "
                         "PREFIX-%%04d.params, the export/foreign "
                         "lane); repeatable — extra models co-host on "
                         "this replica under MX_SERVE_HBM_BUDGET and "
                         "route by the wire envelope's model field")
    ap.add_argument("--epoch", type=int, default=0)
    ap.add_argument("--inputs", default="data",
                    help="comma-separated model input names")
    ap.add_argument("--example-shape", default=None, metavar="DIMS",
                    help="per-row input dims, e.g. '3,224,224' "
                         "(';'-separated for multi-input models)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--demo", action="store_true",
                    help="serve the built-in deterministic demo MLP "
                         "(smokes; tools/serve_load.py verifies "
                         "its outputs)")
    ap.add_argument("--decode", action="store_true",
                    help="also host the deterministic demo LM behind "
                         "the GENERATE verb (continuous-batching "
                         "decode engine; can serve alone or alongside "
                         "--demo)")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--port-base", type=int, default=None,
                    help="bind port-base + MX_PROCESS_ID (multi-replica "
                         "serving under tools/launch.py)")
    ap.add_argument("--buckets", default=None,
                    help="override MX_SERVE_BUCKETS for this replica")
    ap.add_argument("--ready-file", default=None,
                    help="write the bound port here once accepting")
    args = ap.parse_args(argv)

    from ..base import get_env
    from ..device import tpu
    from ..health import Heartbeat
    from .server import ServeServer, serve_forever

    # A replica serves from the accelerator: parameters, KV pools and
    # inputs live on tpu(0) — the chip, or the first host device under
    # the harness's MX_FORCE_CPU=1 pin.  With neither, resolving the
    # device raises and the replica does not start.
    ctx = tpu(0)
    device = ctx.jax_device

    port = args.port
    if port is None and args.port_base is not None:
        rank = int(get_env("MX_PROCESS_ID") or
                   os.environ.get("DMLC_WORKER_ID") or 0)
        port = args.port_base + rank
    if port is None:
        port = int(get_env("MX_SERVE_PORT"))

    # heartbeat-file liveness (launch.py --hang-timeout): beat from the
    # batcher loop, throttled — an IDLE replica is healthy, so the beat
    # must not depend on traffic
    tick = None
    hb_path = get_env("MX_HEARTBEAT_FILE", "")
    if hb_path:
        hb = Heartbeat(hb_path)
        last = [0.0]

        def tick():
            now = time.monotonic()
            if now - last[0] >= 1.0:
                last[0] = now
                hb.beat(0, 0)

        hb.beat(0, 0)

    decode_engine = None
    t_warm0 = time.perf_counter()
    with ctx:
        if args.decode:
            # the GENERATE lane: demo LM + continuous-batching decode pump
            # (ISSUE 15); warm() pre-builds every prefill/decode bucket so
            # serve time pays zero traces.  MX_SERVE_KV_PAGES > 0 selects
            # the PAGED engine (ISSUE 18): shared page heap + block tables,
            # hash-shared prefixes, chunked prefill — same wire surface.
            paged = int(get_env("MX_SERVE_KV_PAGES", 0, int) or 0) > 0
            draft_layers = int(get_env("MX_SERVE_DRAFT", 0, int) or 0)
            if draft_layers > 0:
                # speculative decoding (ISSUE 20): a shallow draft proposes
                # MX_SERVE_SPEC_K tokens per window, the paged target
                # verifies them in ONE multi-position dispatch; co-hosted
                # draft+target share the page heap budget
                if not paged:
                    raise SystemExit("serve: MX_SERVE_DRAFT needs the "
                                     "paged engine (set MX_SERVE_KV_PAGES)")
                from .decode import (DecodeConfig, DraftDecodeServable,
                                     PagedDecodeServable,
                                     SpeculativeDecodeBatcher,
                                     demo_spec_pair)
                cfg = DecodeConfig()
                tparams, dcfg, dparams = demo_spec_pair(
                    cfg, draft_layers=draft_layers)
                decode_engine = SpeculativeDecodeBatcher(
                    PagedDecodeServable(params=tparams, config=cfg),
                    DraftDecodeServable(params=dparams, config=dcfg,
                                        name="demo-lm-draft"),
                    on_tick=tick)
            elif paged:
                from .decode import PagedDecodeBatcher, PagedDecodeServable
                decode_engine = PagedDecodeBatcher(PagedDecodeServable(),
                                                   on_tick=tick)
            else:
                from .decode import DecodeBatcher, DecodeServable
                decode_engine = DecodeBatcher(DecodeServable(),
                                              on_tick=tick)
        state = ServeServer(on_tick=tick, decode=decode_engine)
        sv = None
        specs = _build_servables(args)
        if specs:
            sv, example = specs[0]
            state.host.deploy(sv, example=example)
            for extra_sv, extra_ex in specs[1:]:
                state.add_model(extra_sv, example=extra_ex, on_tick=tick)
        elif not args.decode:
            raise SystemExit("serve: need --model PREFIX, --demo or "
                             "--decode")
    warm_s = time.perf_counter() - t_warm0
    # warm-start visibility: a respawned replica finds its bucket
    # table's XLA compiles in jax's persistent cache — the banner (and
    # the METRICS verb) carries the receipts
    from ..compile_cache import stats as _cc_stats
    cs = _cc_stats()
    if sv is not None:
        print("serve: %s v%d warm on %s (params on %s), %d bucket(s) %r "
              "in %.2fs (compile-cache%s hits=%d misses=%d), port %d"
              % (sv.name, sv.version, device, sv.param_platform(),
                 len(sv.buckets.sizes),
                 list(sv.buckets.sizes), warm_s,
                 "" if cs["dir"] else " off",
                 cs["xla_hits"], cs["xla_misses"], port),
              file=sys.stderr, flush=True)
        if len(specs) > 1:
            rep = state.host.packing_report()
            print("serve: co-hosting %d models %r (used=%d budget=%s)"
                  % (len(rep["models"]), sorted(rep["models"]),
                     rep["used_bytes"],
                     rep["hbm_budget_bytes"] or "off"),
                  file=sys.stderr, flush=True)
    if decode_engine is not None:
        dsv = decode_engine.servable
        ps = decode_engine.page_stats()
        if ps is not None:
            spec = ""
            if ps.get("engine") == "speculative":
                spec = ", speculative: k=%d draft=%s" \
                    % (ps["spec_k"], ps["draft_model"])
            print("serve: decode %s v%d warm (paged: %d pages x %d "
                  "tok, chunk=%d, share=%s%s) in %.2fs (slots=%d, "
                  "max_tokens=%d), port %d"
                  % (dsv.name, dsv.version, ps["kv_pages"],
                     ps["kv_page_len"], ps["prefill_chunk"],
                     "on" if ps["prefix_share"] else "off", spec,
                     warm_s, dsv.config.slots, dsv.config.max_tokens,
                     port),
                  file=sys.stderr, flush=True)
        else:
            print("serve: decode %s v%d warm on %d prompt + %d slot "
                  "bucket(s) in %.2fs (slots=%d, max_tokens=%d, "
                  "page=%d), port %d"
                  % (dsv.name, dsv.version,
                     len(dsv.config.prompt_buckets),
                     len(dsv.config.slot_buckets), warm_s,
                     dsv.config.slots, dsv.config.max_tokens,
                     dsv.config.page, port),
                  file=sys.stderr, flush=True)

    serve_forever(port=port, state=state, ready_file=args.ready_file)
    print("serve: stopped", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
