"""Foundation utilities: env-flag system, registry helpers, error types.

TPU-native rebuild of the roles played by the reference's dmlc-core
(`dmlc/parameter.h` DMLC_DECLARE_PARAMETER reflection, `dmlc::GetEnv` flag
reads, `dmlc/logging.h` CHECK macros) and `python/mxnet/base.py` (ctypes
plumbing).  There is no C ABI here: the framework is Python-first over
jax/jaxlib, so "handle plumbing" reduces to ordinary Python objects.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Callable, Dict, Optional

__all__ = [
    "MXNetError",
    "get_env",
    "set_env",
    "environment",
    "string_types",
    "numeric_types",
    "integer_types",
]


class MXNetError(RuntimeError):
    """Default error type raised by the framework (reference: MXGetLastError)."""


string_types = (str,)
numeric_types = (float, int)
integer_types = (int,)

# ---------------------------------------------------------------------------
# Env-flag system (reference: dmlc::GetEnv + env_var.md catalog).
# Flags are read lazily at first use, like the reference, but we also keep a
# process-local override dict so `mx.util.set_env` / the `environment()` test
# context-manager work without mutating os.environ for spawned workers.
# ---------------------------------------------------------------------------

_env_overrides: Dict[str, Optional[str]] = {}
_env_lock = threading.Lock()

# Canonical flag catalog: name -> (default, docstring). Kept for doc-gen and
# `mx.runtime` feature reporting; unknown MXNET_* flags still read through.
ENV_CATALOG: Dict[str, Any] = {
    "MXNET_ENGINE_TYPE": ("ThreadedEnginePerDevice", "Execution mode: 'NaiveEngine' forces synchronous per-op execution (block_until_ready after every op) for debugging; any other value keeps XLA async dispatch."),
    "MXNET_EXEC_BULK_EXEC_INFERENCE": ("1", "No-op on TPU (XLA fuses); accepted for compat."),
    "MXNET_EXEC_BULK_EXEC_TRAIN": ("1", "No-op on TPU (XLA fuses); accepted for compat."),
    "MXNET_GPU_MEM_POOL_TYPE": ("Round", "No-op: PJRT owns HBM pooling."),
    "MXNET_KVSTORE_BIGARRAY_BOUND": ("1000000", "Gradient bucket size threshold for kvstore collectives."),
    "MXNET_ENFORCE_DETERMINISM": ("0", "Force deterministic kernels."),
    "MXNET_PROFILER_SYNC": ("0", "1 = the profiler blocks until each annotated range's device work completes before stamping its duration (accurate per-range timings at the cost of breaking dispatch overlap)."),
    "MXNET_SAFE_ACCUMULATION": ("1", "Accumulate reductions in fp32 even for fp16/bf16 inputs."),
    "MXNET_DEFAULT_DTYPE": ("float32", "Default dtype for array creation."),
    # rebuild-specific flags (SURVEY §5.6: env vars are the de-facto flag
    # system; this catalog is the canonical doc source — docs/ENV_VARS.md
    # is generated from it by tools/gen_env_docs.py)
    "MX_MODULE_JIT": ("1", "0 disables the whole-graph-jit fast paths (Module fused train step AND Executor inference) - debugging escape hatch back to per-op dispatch."),
    "MX_FORCE_CPU": ("0", "Pin the CPU backend: mx.tpu(i) resolves to the i-th host device and the process never touches the chip (the test harness, data workers, CPU-side clients).  Without it mx.tpu(i) on a machine with no chip is an error."),
    "MX_TEST_CTX": ("", "'tpu' switches the pytest lane to the real chip as default context; with no chip every test fails (tests/conftest.py)."),
    "MX_DATA_DIR": ("", "Root of real-dataset drops (mnist/, ptb/): arms tests/test_real_data.py and the examples' real-data paths."),
    "MX_PRETRAINED_DIR": ("~/.mxnet/models", "Local weight store scanned by model_zoo get_model(..., pretrained=True)."),
    "MX_COORDINATOR": ("", "host:port of process 0 for jax.distributed (set by tools/launch.py)."),
    "MX_NUM_PROCESSES": ("", "Process-group size for jax.distributed (launcher-set)."),
    "MX_PROCESS_ID": ("", "This process's rank (launcher-set)."),
    "MX_INIT_TIMEOUT": ("", "Seconds to bound the jax.distributed coordinator handshake (fail-fast + retry instead of hanging)."),
    "MX_PS_ROOT": ("", "dist_async parameter-server address host:port (single server)."),
    "MX_PS_ROOTS": ("", "Comma-separated PS addresses; keys hash-shard across them (launch.py -s N)."),
    "MX_PS_PORT": ("9600", "Port a kvstore server process binds (DMLC_ROLE=server)."),
    "MX_PS_SNAPSHOT": ("", "Path where a kvstore server persists its store (atomic pickle) after mutations and on STOP; a server restarted with the same path resumes with no data loss."),
    "MX_PS_SNAPSHOT_EVERY": ("1", "Snapshot the server store every N mutating requests (1 = every PUSH/INIT; larger trades durability for throughput)."),
    "MX_KVSTORE_BUCKET_KB": ("4096", "Fusion-bucket capacity in KB for coalesced gradient exchange: a batched push/pull packs small dense keys into flat per-dtype buckets of about this size, so a ResNet-scale step does a few bucket collectives/RPCs instead of ~160 per-key ones; 0 disables bucketing.  The key->bucket layout is a pure function of the ordered (key, shape, dtype) set, so workers and the PS agree with no coordination; the dist_async retry layer replays whole buckets."),
    "MX_GRAD_COMPRESS": ("", "Default gradient-wire compression for Trainers constructed without explicit compression_params: 'int8' (per-block symmetric int8 + error feedback, ~3.9x fewer exchange bytes), '2bit' (reference +-threshold/0 levels + error feedback), or 'bf16' (pure cast, half the bytes).  Empty ships full-width floats.  Launch scripts flip it fleet-wide; per-Trainer compression_params always wins."),
    "MX_GRAD_COMPRESS_BLOCK": ("256", "Elements per int8 scale block for 'int8' gradient compression: each block of this many gradient elements shares one f32 scale (max|block|/127), so the wire payload is n + 4n/block bytes per n-element gradient.  Smaller blocks track outliers tighter at more scale overhead."),
    "MX_STEP_COMPILE": ("0", "1 = whole-program compiled train step: loss forward, backward, the bucketed (int8/2bit error-feedback quantized) gradient exchange, the fused multi-tensor optimizer apply and device-side metric accumulation trace into ONE donated jax.jit per step (mxnet_tpu/step.py CompiledStep; Module.fit picks it up automatically).  First call traces, a shape/dtype change retraces, lr/wd arrive as traced scalars so schedulers never recompile.  Eager remains the debug path; the PS/dist_async transport, unsupported optimizers, grad_req='add' and NaN-policy-armed runs fall back to the eager pipeline automatically."),
    "MX_STEP_SCAN": ("0", "N>1 = scan-window size for the compiled step lane's window consumers (mxnet_tpu.step.scan_window(): tools/dispatch_count.py --compiled and any harness driving CompiledStep.run_window): N prefetched batches stay on device per host round-trip, the step body runs under one lax.scan, and the window costs 1-2 dispatches total (batch transfer + window launch) instead of N; gradient accumulation folds into the scanned body via run_window(accum=k).  Module.fit dispatches per batch regardless (its iterator/callback contract is per-batch).  0/1 = one dispatch per step."),
    "MX_MESH_AXES": ("", "Named mesh axes for the SpecLayout sharded training lane (mxnet_tpu/parallel/speclayout.py), as comma-separated name[=size] tokens, e.g. 'data,fsdp=2' or 'data,fsdp=2,tp=2'.  When set, CompiledStep/Trainer.make_compiled_step build the step as ONE donated SPMD jit over this mesh: the batch splits over data*fsdp, parameters + optimizer state live sheet-sharded (fsdp) / tensor-split (tp) so per-chip state bytes drop ~linearly with the fsdp axis, gradients reduce-scatter onto the parameter shards (int8-quantized per bucket under gradient compression, error-feedback residuals sharded per chip) and XLA all-gathers updated parameters just in time.  An unsized data axis infers -1 (all remaining devices); unsized model axes default to 2.  Empty keeps the replicated step.  Sharding NEVER changes results - only placement and communication."),
    "MX_FSDP": ("", "Size of the fsdp (ZeRO sheet-sharding) mesh axis for the SpecLayout lane.  Overrides the fsdp entry of MX_MESH_AXES; setting MX_FSDP=N alone implies MX_MESH_AXES='data,fsdp=N'.  Per-chip params+optimizer_state bytes in buffer_census() drop ~1/N (acceptance: within 15% of ideal at N=2 and N=4 in dryrun_multichip).  Empty/1 = no fsdp sharding."),
    "MX_EXCHANGE_OVERLAP": ("0", "1 = overlap-scheduled gradient exchange: the Trainer arms per-gradient readiness hooks and each fusion bucket's collective launches the moment backward finalizes the bucket's last member (reverse-parameter-order buckets, so late layers go out first), with results committed at the pre-update drain barrier.  Exchange results are identical to the serialized path (a grad rewritten after launch relaunches its unit at drain); 0 keeps the exchange serialized after backward."),
    "MX_OPTIMIZER_AGGREGATE": ("", "Fused multi-tensor optimizer apply: empty keeps each optimizer's default aggregate_num (SGD/NAG/Adam/AdamW fuse up to 64 params per dispatch by default), 0 opts out back to the per-param update loop, any other N caps how many (weight, grad, state) triples fuse into one jitted pytree dispatch."),
    "MX_KVSTORE_RETRY_DEADLINE": ("60", "dist_async client: total seconds to keep retrying a failed RPC (reconnect + replay) before raising a terminal MXNetError; also bounds the initial connect wait per server at startup (the launcher starts servers concurrently, so workers retry until each binds)."),
    "MX_KVSTORE_RETRY_BASE": ("0.05", "dist_async client: first backoff delay in seconds; doubles per attempt."),
    "MX_KVSTORE_RETRY_MAX": ("2.0", "dist_async client: backoff delay cap in seconds."),
    "MX_KVSTORE_RETRY_JITTER": ("0.2", "dist_async client: uniform jitter fraction added to each backoff delay (decorrelates worker retry storms)."),
    "MX_KVSTORE_RECV_TIMEOUT": ("", "Seconds a kvstore recv_msg may block mid-message before raising TimeoutError (empty = block forever; the dist_async client always bounds its RPCs with this, default 30 there)."),
    "MX_KVSTORE_BARRIER_TIMEOUT": ("120", "Seconds a kvstore server BARRIER waits for stragglers before failing the barrier."),
    "MX_KVSTORE_HEARTBEAT": ("5", "dist_async client: seconds between background PINGs to each server (0 disables); keeps a compute-bound worker from being evicted as stale."),
    "MX_KVSTORE_STALE_TIMEOUT": ("30", "kvstore server: a worker silent this many seconds is evicted from barrier accounting so a wedged peer cannot hold BARRIER forever."),
    "MX_FAULT_INJECT": ("", "Fault-injection spec 'site:action[:k=v,...];...' armed at import (tools/launch.py --fault); see mxnet_tpu/fault.py."),
    "MX_NAN_POLICY": ("", "fit-loop gradient guard (mxnet_tpu/health.py): 'warn' logs non-finite gradients, 'skip_batch' additionally drops the poisoned update so params stay finite, 'raise' fails the rank fast for the supervisor to restart; empty disables."),
    "MX_STEP_TIMEOUT": ("", "Seconds a training step may stall before the watchdog thread dumps every thread's stack to stderr and exits the process with code 86, so tools/launch.py --restart on-failure restarts the rank from its last checkpoint; empty disables."),
    "MX_HEARTBEAT_FILE": ("", "Per-rank liveness file the fit loop atomically rewrites every batch; tools/launch.py --hang-timeout sets it per worker and reads the mtime to tell a slow rank (fresh file) from a wedged one (stale file, killed + restarted)."),
    "MX_RECORDIO_TOLERATE_CORRUPT": ("0", "1 = a corrupt/truncated .rec record (e.g. a tail torn by a mid-write crash) is skipped-and-counted (reader.corrupt_skipped) and reads end there, instead of raising OSError with the uri and byte offset."),
    "MX_TELEMETRY": ("1", "Runtime telemetry (mxnet_tpu/telemetry.py): 1 (default) records per-phase step histograms (data_wait/forward/backward/exchange/optimizer_apply/metric_update/metric_drain/retrace/compiled_step) into the process-wide instrument registry and appends one flight-recorder step record per training step (phase durations, dispatch/wire deltas, retry + NaN-guard hits, throughput); 0 disables both (spans become shared no-ops).  Engine counters (dispatch_count, wire_bytes, compiled_steps) live in the registry regardless - this flag gates only the span/record layer."),
    "MX_TELEMETRY_TRACE": ("", "Directory for per-process distributed trace files: when set, every span (step phases, kvstore client RPCs, server handling incl. retry/replay events, causally linked by wire-propagated trace/span IDs) is buffered and flushed to <dir>/trace-<role>-r<rank>-p<pid>.trace.json at process exit; tools/telemetry_dump.py merges the per-worker files into one chrome-trace timeline.  Empty disables span buffering (tests force it via telemetry.start_tracing())."),
    "MX_TELEMETRY_RING": ("256", "Flight-recorder capacity: the telemetry ring keeps the last N structured step records, dumped to MX_CRASH_DIR on watchdog/NaN/fit failure and summarized (step, throughput, last-exchange bytes) in the heartbeat file's JSON payload for the supervisor's fleet status table."),
    "MX_CRASH_DIR": ("", "Crash-dump directory: on a watchdog trip, an MX_NAN_POLICY=raise gradient guard, a fit-loop exception, or a supervisor-observed rank failure, the flight-recorder ring + a counters snapshot are written to <dir>/crash-rank<r>-pid<p>-<n>.json (the supervisor adds supervisor-<proc>-<n>.json with what it saw: exit code, restarts, last heartbeat payload).  Empty disables crash dumps."),
    "MX_SERVE_BUCKETS": ("1,2,4,8,16", "Serving engine (mxnet_tpu/serve): comma-separated batch-size buckets the AOT compiler pre-traces per servable version.  Every batch the micro-batcher dispatches is padded up to the smallest bucket that fits, so serve-time never pays a trace; requests larger than the top bucket are rejected at admission."),
    "MX_SERVE_MAX_BATCH": ("16", "Serving engine: the micro-batcher coalesces queued requests into one dispatch of at most this many rows (clamped to the top MX_SERVE_BUCKETS bucket).  Larger batches amortize dispatch overhead at higher per-request latency."),
    "MX_SERVE_MAX_DELAY_US": ("2000", "Serving engine: microseconds the micro-batcher holds an under-full batch open for more arrivals before dispatching what it has.  0 dispatches immediately (no coalescing).  The wait rides the mxnet_tpu.fault injectable clock, so virtual-time tests drive the coalescing window deterministically."),
    "MX_SERVE_QUEUE_CAP": ("256", "Serving engine: admission-queue bound in ROWS (requests' batch rows, not request count).  A submit that would exceed it is rejected immediately with an explicit overload error (counted in serve.rejected) instead of queueing into unbounded latency - load shedding is the backpressure contract."),
    "MX_SERVE_PORT": ("9700", "Port a serving replica binds (python -m mxnet_tpu.serve); with --port-base under the launcher each rank serves on port-base + MX_PROCESS_ID."),
    "MX_SERVE_ROOTS": ("", "Comma-separated serving replica addresses host:port the ServeClient connects to; the client sticks to one replica and fails over to the next on a connection error or timeout (SEQ retry makes the replay safe)."),
    "MX_SERVE_TIMEOUT": ("30", "Seconds a serving client waits for one PREDICT reply (queue wait + dispatch included) before treating the replica as dead and failing over; also the server-side bound on a request waiting out its batch future."),
    "MX_SERVE_REPLAY_CAP": ("512", "Serving replica: bound on the exactly-once replay cache (one entry per client id).  Entries are kept in LRU order - every new seq or replay hit from a client moves it to the recent end - and over-cap inserts evict the least-recently-touched RESOLVED entries (in-flight entries are never dropped); each eviction is counted in serve.replay_evicted.  Values < 1 clamp to 1 (the exactly-once contract needs at least the in-flight entry; 0 never means 'unbounded').  Serving clients are ephemeral uuids, so without this bound every dead client's last PREDICT response would be retained forever."),
    "MX_SERVE_DECODE_SLOTS": ("8", "Decode engine (mxnet_tpu/serve/decode.py): number of concurrent generation slots in the device-resident KV-cache pool.  The pool is allocated once at deploy (owner 'kv_cache' in the buffer census) and donated through every decode step, so HBM stays flat; decode programs are AOT-bucketed by active-slot count (powers of two up to this), and the continuous-batching pump packs all active sequences into the smallest covering bucket each step - one device dispatch per decode step regardless of the active count."),
    "MX_SERVE_DECODE_MAX_TOKENS": ("32", "Decode engine: cap on generated tokens per GENERATE request (a request's max_tokens clamps to this).  Together with the top prompt bucket it sizes each slot's KV page capacity."),
    "MX_SERVE_DECODE_PAGE": ("16", "Decode engine: KV page size in token positions.  Each slot's cache extent (top prompt bucket + max tokens + the pipeline-overrun margin) rounds up to whole pages; retiring a sequence 'evicts' its pages by bookkeeping alone (lengths reset on slot reuse, stale entries masked) - the pool itself is never reallocated."),
    "MX_SERVE_DECODE_PROMPT_BUCKETS": ("4,8,16", "Decode engine: comma-separated prompt-length buckets the prefill program table pre-compiles.  A GENERATE prompt pads up to the smallest covering bucket (one prefill dispatch per admitted sequence); prompts longer than the top bucket are rejected at admission, so serve time never pays a trace."),
    "MX_SERVE_KV_PAGES": ("0", "Paged decode engine (ISSUE 18): number of physical pages in the shared KV page heap (layers, kv_pages, kv_page_len, heads, head_dim), owner 'kv_pages' in the buffer census.  0 (default) auto-sizes to (slots+1) * pages-per-slot - the same HBM the flat pool would take - but because sessions only hold the pages their actual length needs, the same heap admits several times more mixed-length sessions.  > 0 on 'python -m mxnet_tpu.serve --decode' also SELECTS the paged engine (the flat pool stays the default).  Page 0 is reserved scratch."),
    "MX_SERVE_KV_PAGE_LEN": ("0", "Paged decode engine: token positions per physical KV page.  0 (default) inherits MX_SERVE_DECODE_PAGE.  Smaller pages pack mixed-length sessions tighter and share longer prefixes (only FULL pages are hash-shared); larger pages cut block-table and gather overhead."),
    "MX_SERVE_PREFIX_SHARE": ("1", "Paged decode engine: 1 (default) hash-shares read-only full prompt pages across sessions - a rolling content hash over token ids is chained at page boundaries, equal hashes adopt the donor's pages via refcounts, and a session diverging inside a shared page forks it copy-on-write - so N sessions over one system prompt prefill only their suffixes.  0 disables sharing (every admission prefills all its pages)."),
    "MX_SERVE_PREFILL_CHUNK": ("0", "Paged decode engine: prefill chunk length in token positions (rounded up to whole pages; 0 = one page).  Long prompts prefill as a train of page-aligned chunks that INTERLEAVE with decode steps inside the pump's one-dispatch-per-tick cadence, so a 10k-token admission never stalls in-flight generations for more than one chunk-step."),
    "MX_SERVE_SPEC_K": ("4", "Speculative decoding (ISSUE 20): tokens the draft model proposes per speculative window.  Each window costs spec_k draft dispatches (on the draft's own tiny KV pool) + ONE multi-position verify dispatch on the paged target, which accepts the longest agreeing prefix and emits the target's own argmax after it - so 1..spec_k tokens commit per verify with output BIT-IDENTICAL to non-speculative greedy decode regardless of draft quality.  Clamped to [1, 8] (the page-overrun margin the verify scatter needs)."),
    "MX_SERVE_DRAFT": ("0", "Speculative decoding: number of layers in the built-in draft model for 'python -m mxnet_tpu.serve --decode'.  > 0 co-hosts a shallow draft (the target demo LM's first N layers, shared embeddings - see demo_spec_pair) next to the paged target and selects the speculative engine; requires MX_SERVE_KV_PAGES > 0.  0 (default) disables speculation."),
    "MX_SERVE_HBM_BUDGET": ("0", "Census-driven multi-model bin-packing (ISSUE 20): HBM byte budget one serving replica may spend across every co-hosted model (deployed servables + decode engines' target/draft).  ModelHost.deploy measures each candidate AFTER its warm - live param/state bytes plus the peak memory_analysis temp bytes of its registered programs - and refuses admission with a typed in-band '(False, \"budget: ...\")' wire reply when hosted + new would bust the budget.  0 (default) disables the packer (admission is unbounded)."),
    "MX_PROGRAM_CENSUS": ("1", "XLA program census (mxnet_tpu/programs.py): 1 (default) routes every jit-creation site through the process-wide program registry - per-program compile-time histograms (program_compile_seconds{program}), XLA memory_analysis/cost_analysis metadata (program_temp_bytes/program_flops, where the backend provides them), retrace counts with a structured retrace-explainer diff (which arg's shape/dtype/tree structure changed), and the jax.live_arrays() device-buffer census bucketed by owner (params/optimizer_state/ef_residuals/serve/other) riding flight-recorder records and crash dumps.  0 makes register_program a plain jax.jit and disables the census."),
    "MX_LEAK_WARN_BYTES": ("67108864", "Buffer-census leak detector threshold: when total live device bytes grow monotonically across consecutive census checks by more than this many bytes, the census_leak_bytes gauge latches the streak, census.leak_trips increments and a warning names the growing owner buckets.  Any shrink resets the streak; 0 disables the trip (gauges still publish)."),
    "MX_FLEET_INTERVAL": ("2.0", "Fleet collector (mxnet_tpu/fleet.py): seconds between scrape rounds over every registered member (serve replicas + PS servers via the METRICS wire verb, training workers via their heartbeat files' JSON payload).  A member that fails its scrape is marked absent on that same round.  0 disables the embedded supervisor collector."),
    "MX_FLEET_RING": ("120", "Fleet collector: bounded time-series ring of merged fleet snapshots (one entry per scrape round, keyed (role, rank, instrument) inside).  The straggler/SLO detectors and tools/fleet_top.py read the ring; the newest entry rides supervisor crash dumps as the `fleet` section."),
    "MX_FLEET_WINDOW": ("5", "Fleet detectors: sliding-window length in scrape rounds for straggler step-time medians and SLO burn (rolling p50/p99, rejection-rate) computation.  Short windows react faster; long windows smooth transients."),
    "MX_FLEET_STRAGGLER_FACTOR": ("2.0", "Straggler detector: a worker whose windowed step duration exceeds this multiple of the fleet (lower-)median is flagged — fleet.stragglers gauge, a flight-recorder event and a structured warning naming the rank and its dominant phase (e.g. data_wait)."),
    "MX_FLEET_STALE": ("", "Fleet collector: seconds a heartbeat-scraped member's beat may age before the member is marked absent.  Empty = auto: max(2x MX_FLEET_INTERVAL, 30s) - beats are per BATCH, so the floor stays above slow-rank step times (a 6s-step straggler must be NAMED, not flap absent).  Wire-scraped members (serve/PS) are instead marked absent on scrape failure."),
    "MX_FLEET_SLO_P50_MS": ("", "Serving SLO target: fleet-merged rolling p50 of the MX_FLEET_SLO_PHASES histograms in milliseconds.  fleet.slo_burn{slo=p50_latency} publishes observed/target; burn > 1 latches a breach event.  Empty disables this tracker."),
    "MX_FLEET_SLO_P99_MS": ("", "Serving SLO target: fleet-merged rolling p99 latency in milliseconds (same burn/latch semantics as MX_FLEET_SLO_P50_MS).  Empty disables."),
    "MX_FLEET_SLO_REJECT_RATE": ("", "Serving SLO target: windowed fleet rejection-rate bound (rejected / (requests+rejected), from merged serve.* counter deltas).  Burn = observed/target into fleet.slo_burn{slo=rejection_rate}; > 1 latches.  Empty disables."),
    "MX_FLEET_SLO_QUEUE": ("", "Serving SLO target: mean fleet queue depth bound (rows, from merged serve.queue_rows gauges).  Burn = observed/target into fleet.slo_burn{slo=queue_depth}; > 1 latches.  Empty disables."),
    "MX_FLEET_SLO_PHASES": ("queue_wait,serve_dispatch", "Comma-separated step_phase_seconds phases whose fleet-merged histograms define the serving latency distribution the SLO p50/p99 trackers read (bucket-wise exact merge; identical boundaries required)."),
    "MX_PREFETCH_DEPTH": ("2", "DevicePrefetcher queue bound in batches: how many device-resident batches may sit ahead of the consumer (2 = classic double buffering).  The producer blocks (stop-aware bounded polls) at the bound, so prefetch can never balloon memory by more than this many batches."),
    "MX_ELASTIC": ("0", "Elastic membership (mxnet_tpu/kvstore): 1 = a dist_async worker announces itself with the JOIN wire verb at store init (idempotent for ranks the server already seeded) and the Module.fit loop installs a SIGTERM drain handler — on preemption notice the rank finishes its epoch, checkpoints, sends LEAVE and exits 0, so the barrier quorum shrinks instead of timing out.  tools/launch.py --elastic sets it for every worker.  0 keeps the fixed-membership behavior."),
    "MX_ELASTIC_EPOCH": ("0", "The membership epoch a worker incarnation plans its fusion buckets under (the bucket-name CRC salt).  Set by tools/launch.py --elastic on every (re)spawned worker after a resize, so all workers of one incarnation derive identical salted bucket names with no coordination; 0 keeps the historical unsalted names."),
    "MX_ELASTIC_EVICT_AFTER": ("", "kvstore server: a MEMBER rank silent this many seconds is evicted from the live membership table itself (an involuntary LEAVE with a membership-epoch bump) instead of only being discounted from the current barrier - shrink-and-continue for workers that died without preemption notice.  Empty/0 disables permanent eviction (transient stale discounting via MX_KVSTORE_STALE_TIMEOUT still applies)."),
    "MX_EXCHANGE_HIERARCHICAL": ("0", "1 = two-tier gradient exchange on the dist_async store (gradient/accumulate mode): tier 1 merges device copies locally (ICI), tier 2 ships int8 both ways across the slice boundary - the existing compressed PUSH plus the PULLQ quantized return leg - with each fusion bucket's pull launched as-ready on its own connection (a straggling server shard delays only its own buckets).  Cross-slice wire bytes drop ~4x vs the flat fp32 pull; the pull leg's quantization error is stateless (no error feedback), so this is an opt-in for the accumulate exchange, never the default."),
    "MX_EXCHANGE_PARALLEL": ("4", "Concurrent as-ready bucket pulls (dedicated connections) per worker under MX_EXCHANGE_HIERARCHICAL."),
    "MX_FLEET_PORT": ("", "Port the fleet collector's wire server binds (FLEET verb -> merged snapshot as a JSN payload, METRICS -> whole-fleet federation exposition; same length-prefixed envelope as the kvstore/serve wire).  This is the API surface the coming serve router/autoscaler consume.  Empty = no wire server."),
    "MX_FLEET_HTTP_PORT": ("", "Port of the collector's Prometheus federation HTTP endpoint: GET /metrics returns every member's instruments re-labeled {role,rank,model} plus the fleet rollups — a single scrape covers the whole fleet; GET /fleet.json returns the merged snapshot.  Empty = no HTTP endpoint."),
    "MX_SERVE_DRAIN_TIMEOUT": ("30", "Serving replica drain-not-kill retirement (ISSUE 17): default bounded deadline in seconds a DRAIN verb without an explicit timeout arms.  Admission closes immediately (fresh PREDICT/GENERATE answered '(False, draining: ...)' so routers/clients re-route), in-flight requests and generations finish, then the serve loop exits cleanly; past the deadline the stragglers' connections are severed with NO reply so their clients fail over and re-prefill on a survivor.  A re-asserted DRAIN keeps the FIRST deadline (a retry cannot extend retirement)."),
    "MX_ROUTER_PORT": ("9800", "Port the serving front-tier router binds (python -m mxnet_tpu.serve.router) when --port is not given.  Clients point MX_SERVE_ROOTS at this one address and the router forwards their SEQ envelopes verbatim across the replica set."),
    "MX_ROUTER_REPLICAS": ("", "Comma-separated static replica addresses host:port the router seeds its membership with (the dynamic complement is MX_ROUTER_REPLICAS_FILE).  New members join 'up' optimistically; the first failed forward demotes them to 'dead' and a connect-probe per refresh tick revives them."),
    "MX_ROUTER_REPLICAS_FILE": ("", "Path of the authoritative replica-list file (one host:port per line, '#' comments) the router re-reads every refresh tick.  tools/launch.py --route rewrites it atomically as the autoscaler spawns and retires replicas: an addr that appears joins 'up', one that disappears goes 'draining' (nothing new routed there) until dead, then is forgotten."),
    "MX_ROUTER_REFRESH": ("1.0", "Seconds between router refresh ticks: replicas-file re-read, dead-replica connect probes, and the FLEET snapshot pull that feeds least-loaded routing.  Also the router's heartbeat cadence under the launcher's --hang-timeout."),
    "MX_ROUTER_FLEET": ("", "Fleet collector wire address host:port the router pulls merged load signals from (fleet.replica_signals projection: queue depth, decode admission queue, decode slot occupancy, KV headroom).  Empty = no signals; routing degrades to round-robin over 'up' replicas (a fresh replica with no scrape history scores 0 = idle, which is correct)."),
    "MX_ROUTER_PIN_CAP": ("4096", "Bound on the router's session-pin LRU (client_id -> replica).  Serving clients are ephemeral uuids, so pins must age out; evicting a pin costs decode locality on that session's NEXT request (it re-routes least-loaded and re-pins), never correctness.  Values < 1 clamp to 1."),
    "MX_ROUTER_DRAIN_TIMEOUT": ("30", "Default bounded deadline in seconds for draining the ROUTER itself (DRAIN verb to the router): new sessions are refused 'draining: ...' while pinned sessions keep flowing; the router exits once the wire is idle, and past the deadline straggler connections are severed so their clients replay elsewhere."),
    "MX_AUTOSCALE_UP_BURN": ("1.0", "Autoscaler (tools/launch.py --autoscale MIN:MAX): scale UP when any fleet SLO burn (fleet.slo_burn, observed/target from the merged snapshot) meets/exceeds this for MX_AUTOSCALE_HOLD consecutive supervisor ticks.  Spawns one warm replica per decision (compile-cache restarts make this seconds, not minutes) and registers it with the collector + the router's replicas file."),
    "MX_AUTOSCALE_DOWN_BURN": ("0.5", "Autoscaler: scale DOWN (retire-and-drain ONE replica) when every tracked SLO burn stays at/below this for MX_AUTOSCALE_HOLD consecutive ticks.  The gap between UP_BURN and DOWN_BURN is the hysteresis band that keeps the fleet from flapping; retirement is always drain-not-kill (DRAIN verb, bounded deadline, supervisor treats the clean exit as expected)."),
    "MX_AUTOSCALE_HOLD": ("3", "Autoscaler: consecutive supervisor autoscale ticks a burn signal must hold before acting (both directions).  Raising it trades reaction time for stability; 1 reacts on a single tick."),
    "MX_AUTOSCALE_COOLDOWN": ("10", "Autoscaler: base seconds of the post-action cooldown.  Each action arms fault.RetryPolicy-style backoff (base * 2^consecutive-same-direction-actions, jittered, capped at 8x) before the next action may fire, so a spike absorbs with a burst of spawns but repeated flip-flops back off exponentially."),
}


# ``jax.ad_checkpoint.checkpoint_name`` tag of a value a recomputed block
# (``gluon.Block.recompute``) must KEEP from its forward pass instead of
# computing it again.  Two kinds of value earn it: a discrete decision (a
# router's top-k choice) that a second run of the same arithmetic, fused
# and rounded otherwise by XLA, can make the other way - the backward pass
# would then differentiate another function than the forward pass ran
# (PERF.md section 6, PR 28) - and a kernel's result that costs far more
# time to make again than bytes to hold (the flash kernels' output and
# logsumexp, an expert layer's sort order and chosen scores: PERF.md
# section 6, PR 31 and PR 36).
RECOMPUTE_KEEP = "mx_recompute_keep"

# trace-time state of the thread: how many recomputed blocks enclose the
# code being traced (`blocks`), and the tallies of the programs being
# traced, outermost first (`tallies`)
_recompute_trace = threading.local()


@contextlib.contextmanager
def recomputed_block_trace():
    """``with`` around the trace of one recomputed block's forward."""
    _recompute_trace.blocks = getattr(_recompute_trace, "blocks", 0) + 1
    try:
        yield
    finally:
        _recompute_trace.blocks -= 1


class recompute_tally:
    """``with`` around one trace of a program: `values` and `bytes` (per
    shard under ``shard_map``) of what it tags `recompute_keep`.  A
    whole program's tally counts under a recomputed block only.  An
    operator's own program (`always`) counts whatever it tags: jax keeps
    its trace and hands it to later calls, inside a recomputed block or
    not, and each call's tally joins the enclosing program's."""

    def __init__(self, always: bool = False):
        self.always, self.values, self.bytes = always, 0, 0

    def __enter__(self):
        if not hasattr(_recompute_trace, "tallies"):
            _recompute_trace.tallies = []
        _recompute_trace.tallies.append(self)
        return self

    def __exit__(self, *exc):
        _recompute_trace.tallies.pop()
        return False

    def join(self, values: int, nbytes: int) -> None:
        self.values += values
        self.bytes += nbytes


def recompute_counting() -> Optional[recompute_tally]:
    """The innermost open tally if it counts here, else None."""
    tallies = getattr(_recompute_trace, "tallies", None)
    if tallies and (tallies[-1].always
                    or getattr(_recompute_trace, "blocks", 0)):
        return tallies[-1]
    return None


def recompute_keep(x, count: bool = True):
    """`x` tagged ``RECOMPUTE_KEEP``: outside ``jax.checkpoint`` an
    identity.  Its bytes go to the census of the program being traced
    (``programs.program_summary()``: ``recompute_kept_values``,
    ``recompute_kept_bytes``), which reads what its recomputed blocks
    keep.  `count` False where the same value is tagged a second time in
    a derivative rule, which jax traces later and once for equal calls."""
    from jax.ad_checkpoint import checkpoint_name
    tally = recompute_counting() if count else None
    if tally is not None:
        tally.join(1, x.size * x.dtype.itemsize)
    return checkpoint_name(x, RECOMPUTE_KEEP)


def get_env(name: str, default: Any = None, dtype: Callable = str) -> Any:
    """Read an env flag with overrides (reference: dmlc::GetEnv)."""
    with _env_lock:
        if name in _env_overrides:
            val = _env_overrides[name]
        else:
            val = os.environ.get(name)
    if val is None:
        if default is None and name in ENV_CATALOG:
            default = ENV_CATALOG[name][0]
        if default is None:
            return None
        val = default
    try:
        if dtype is bool:
            return str(val).lower() in ("1", "true", "yes", "on")
        return dtype(val)
    except (TypeError, ValueError):
        return default


def set_env(name: str, value: Optional[str]) -> None:
    """Set (or with None, unset) a process-local env override.  NB this
    keeps os.environ in sync, which hot-path caches (engine.is_naive's
    value-compare) rely on.  Unsetting REMOVES the override entirely —
    a lingering ``None`` entry would shadow every later direct
    ``os.environ`` write (e.g. pytest ``monkeypatch.setenv``) behind
    the catalog default forever."""
    with _env_lock:
        if value is None:
            _env_overrides.pop(name, None)
            os.environ.pop(name, None)
        else:
            _env_overrides[name] = str(value)
            os.environ[name] = str(value)


class environment:
    """Context manager scoping env-var changes (reference:
    python/mxnet/test_utils.py (environment))."""

    def __init__(self, *args):
        if len(args) == 1 and isinstance(args[0], dict):
            self._kwargs = dict(args[0])
        elif len(args) == 2:
            self._kwargs = {args[0]: args[1]}
        else:
            raise ValueError("environment() takes (name, value) or a dict")
        self._saved: Dict[str, Optional[str]] = {}

    def __enter__(self):
        for k, v in self._kwargs.items():
            self._saved[k] = os.environ.get(k)
            set_env(k, v)
        return self

    def __exit__(self, *exc):
        for k, v in self._saved.items():
            set_env(k, v)
        return False


# ---------------------------------------------------------------------------
# The CPU pin.  MX_FORCE_CPU=1 is the test harness's (and the data
# workers') switch: mx.tpu(i) means the i-th host device (device.py) and
# jax is held to the cpu backend.  Nothing else maps the accelerator onto
# the host: without the pin, a missing chip is an error.
# ---------------------------------------------------------------------------

def force_cpu() -> bool:
    """MX_FORCE_CPU is set: the harness's pin is on."""
    return bool(get_env("MX_FORCE_CPU", dtype=bool))


def pin_cpu() -> None:
    """Hold jax to the cpu backend: MX_FORCE_CPU=1 set without
    JAX_PLATFORMS=cpu must still keep the process off the chip."""
    import jax
    jax.config.update("jax_platforms", "cpu")
