"""Runtime configuration — the ``MXT_*`` env-var tier (SURVEY §5 config
tier 2; ref: docs/faq/env_var.md — ~80 MXNET_* vars read via dmlc::GetEnv
at use sites. Here every variable is DECLARED in one registry with type,
default, and doc, read via :func:`get`).

Variables whose reference meaning is owned by XLA/JAX (engine thread
counts, GPU memory pool knobs, exec bulking) have no analog — the XLA
runtime owns scheduling and memory. What remains meaningful on TPU is
declared below; ``describe()`` prints the table (the env_var.md analog).
"""
from __future__ import annotations

import os
from collections import namedtuple

from .base import MXNetError

__all__ = ["get", "set_default", "describe", "variables", "naive_engine",
           "is_set", "change_epoch"]

_Var = namedtuple("_Var", ["name", "type", "default", "doc"])

_REGISTRY = {}


def _declare(name, typ, default, doc):
    _REGISTRY[name] = _Var(name, typ, default, doc)


_declare("MXT_PROFILER_AUTOSTART", bool, False,
         "Start a jax.profiler trace at import "
         "(ref: MXNET_PROFILER_AUTOSTART).")
_declare("MXT_TEST_TPU", bool, False,
         "Enable the hardware test lane (pytest -m tpu).")
_declare("MXT_COORDINATOR", str, None,
         "jax.distributed coordinator address, set by tools/launch.py "
         "(ref: DMLC_PS_ROOT_URI/PORT).")
_declare("MXT_NUM_WORKERS", int, 1,
         "World size under tools/launch.py (ref: DMLC_NUM_WORKER).")
_declare("MXT_WORKER_ID", int, 0,
         "This process's rank under tools/launch.py "
         "(ref: DMLC_WORKER_ID).")

_declare("MXT_FUSED_TRAINER", bool, True,
         "Fuse Trainer.step's per-parameter optimizer updates into ONE "
         "donated XLA launch when eligible (sgd/nag/adam/adamw, dense "
         "grads, no dist kvstore). 0 falls back to eager per-param "
         "updates.")

_declare("MXT_FUSED_STEP", bool, True,
         "Fuse the whole canonical Gluon train step (forward + backward + "
         "optimizer update) into ONE donated XLA launch via "
         "gluon.CachedTrainStep / Trainer.fuse_step, and fuse "
         "Module.update's per-param loop the same way. Eligibility mirrors "
         "MXT_FUSED_TRAINER (supported optimizer, dense grads, single "
         "process, no dist kvstore); 0 forces the eager "
         "record/backward/step path everywhere.")

_declare("MXT_RNN_WAVEFRONT", bool, False,
         "Run multi-layer unidirectional LSTM as a diagonal wavefront: "
         "all layers' recurrent gemms batch into one einsum per diagonal "
         "(serial chain T+L-1 instead of L*T). Off until measured on "
         "chip; numerics identical to the sequential path.")

_declare("MXT_RNN_UNROLL", int, None,
         "Unroll factor for the fused-RNN recurrent scan (0 disables "
         "unrolling; unset = auto: full unroll up to T=128, else 16). "
         "Unrolling amortizes per-iteration loop overhead on the TPU.")

_declare("MXT_KVSTORE_SECRET", str, None,
         "Shared secret authenticating dist_async parameter-server "
         "frames (HMAC-SHA256 over nonce|dir|seq|payload). Required for "
         "any non-loopback server bind; see async_server.py threat "
         "model.")

_declare("MXT_TUNE_TABLE", str, None,
         "Path of the persistent kernel-tuning table (tuning/table.py): "
         "per-(op, shape-bucket, dtype, device) block configs, "
         "XLA-vs-Pallas decisions, and recorded warmup shape "
         "signatures, as versioned JSON. Unset keeps the table "
         "in-memory only (decisions still cached for the process).")
_declare("MXT_TUNE_MODE", str, "heuristic",
         "Where a kernel's tiles come from (ref: "
         "MXNET_CUDNN_AUTOTUNE_DEFAULT; nothing is timed here): "
         "'heuristic' = the tuning table's entry for the shape, else the "
         "deterministic cost model's choice, recorded there "
         "(tuning/autotune.py); 'off' = the cost model alone, the table "
         "neither read nor written, in every resolver alike. Any other "
         "value is an MXNetError at the first kernel dispatch.")

_declare("MXT_COMPILE_CACHE_DIR", str, None,
         "Directory for JAX's persistent compilation cache. When set, "
         "every XLA compile is cached on disk keyed by program+config, "
         "so a resumed trainer or fresh serving replica deserializes "
         "instead of recompiling. tuning.warmup() plus this cache = zero "
         "hot-path JIT in a warm-started process. Ignored where "
         "JAX_COMPILATION_CACHE_DIR is set: the cache then lives there "
         "and no directory is set in code. Give it one fixed path (the "
         "path is part of the cache key).")

_declare("MXT_MAX_INFLIGHT", int, 2,
         "Depth of the async dispatch window (engine.py): the host may "
         "run up to K fused steps ahead of the device before a deferred "
         "host read (non-finite flag, step token) retires the oldest "
         "in-flight step. 1 = synchronous (one host read per step, the "
         "pre-async behavior); capped at 15 (the flag-mask width). "
         "engine.bulk/set_bulk_size override it per scope — the "
         "ThreadedEngine bulking knob made real.")

_declare("MXT_SKIP_NONFINITE", bool, False,
         "Skip the optimizer update (weights, optimizer state, step "
         "counter all untouched) whenever any gradient is non-finite. "
         "Eager Trainer.step/Module.update run one fused multi_all_finite "
         "check; the fused CachedTrainStep compiles the guard into its "
         "single launch via lax.cond (read when the fused program builds). "
         "Skips land in the 'skipped_nonfinite_steps' profiler counter.")

_declare("MXT_FAULT", str, None,
         "Deterministic fault injection (resilience.py), e.g. "
         "'kv_drop:p=0.5,seed=7,n=10;kv_delay:p=0.2,ms=5;"
         "ckpt_crash:at=manifest,n=1'. kv_drop/kv_delay hit kvstore "
         "network ops; ckpt_crash raises SimulatedCrash at a named "
         "CheckpointManager write phase (params|states|manifest|rotate); "
         "hb_drop loses membership heartbeats on the wire, "
         "worker_freeze:worker=I[,after=K] freezes worker I's heartbeat "
         "thread (zombie emulation), rejoin_race:ms=N widens the "
         "server-side re-registration fencing window; "
         "replica_kill:replica=I[,after=K] kills serving replica I at "
         "its Kth router tick (in-flight requests fail over), "
         "replica_slow:replica=I,ms=N[,after=K] stalls replica I's "
         "decode for N ms (hedge bait); "
         "data_host_kill:host=I[,after=K] kills host I's data-plane "
         "decode fleet at its Kth chunk-commit boundary (survivors "
         "steal its reclaimed chunks), "
         "data_worker_slow:host=I,ms=N slows host I's decode by N ms "
         "per chunk (steal bait); "
         "traffic_storm:rps=N,after=K[,tenant=T] flips the synthetic "
         "serving TrafficGenerator to N req/s after its Kth tick "
         "(optionally all attributed to tenant T) — the seeded flash "
         "crowd the autoscaler must absorb; "
         "replica_spawn_slow:ms=N makes every autoscaler-spawned spare "
         "take N ms extra to warm before it may go routable (the "
         "router must keep serving off the existing tier meanwhile); "
         "grad_spike:layer=N,after=K[,scale=S] multiplies layer N's "
         "gradient by S (default 1e4) ON DEVICE once the fused step's "
         "dispatch count passes K — the seeded anomaly the training-"
         "health detectors (health.py) must catch within one "
         "InflightWindow retirement.")

_declare("MXT_HEALTH", bool, False,
         "Training-health plane (health.py): the fused train step "
         "computes per-layer grad-norm / param-norm / update-ratio "
         "stats INSIDE its one donated launch and stages them into the "
         "async dispatch window, so K steps of stats cost the SAME one "
         "deferred read the engine already performs (syncs/step is "
         "bit-equal on vs off — tests/test_health.py asserts it). "
         "Host-side detectors run at window retirement: loss-spike "
         "(z-score vs EMA), grad-explosion/vanish, dead-layer. Read "
         "when the fused program builds, like MXT_SKIP_NONFINITE.")
_declare("MXT_HEALTH_SPIKE_Z", float, 6.0,
         "Loss-spike z-score threshold: |loss - EMA| > z * stddev "
         "(after the EMA warmup) fires a 'loss_spike' anomaly.")
_declare("MXT_HEALTH_EXPLODE", float, 1e3,
         "Per-layer gradient-norm ceiling: a grad L2 norm above this "
         "(or non-finite) fires a 'grad_explosion' anomaly.")
_declare("MXT_HEALTH_VANISH", float, 1e-8,
         "Per-layer gradient-norm floor: a grad L2 norm below this "
         "counts one vanish tick; MXT_HEALTH_DEAD_STEPS consecutive "
         "ticks fire a 'dead_layer' anomaly.")
_declare("MXT_HEALTH_DEAD_STEPS", int, 3,
         "Consecutive vanished-gradient steps before a layer is "
         "declared dead (health.py dead-layer detector).")
_declare("MXT_HEALTH_EMA_DECAY", float, 0.9,
         "EMA decay for the host-side loss mean/variance tracker the "
         "loss-spike detector compares against.")
_declare("MXT_HEALTH_GUARD_HOOK", bool, False,
         "Let health anomalies join the MXT_SKIP_NONFINITE guard "
         "bookkeeping: a grad_explosion anomaly also lands in the "
         "skipped_nonfinite_steps counter path (host bookkeeping only "
         "— numerics are NEVER touched by the detector; the on-device "
         "skip remains the guard's own lax.cond).")
_declare("MXT_HEALTH_SKEW_RATIO", float, 1.5,
         "Fleet skew-watch straggler threshold: slowest member step "
         "time / fleet median above this ratio reads as a straggler "
         "verdict (health.fleet_skew over the FleetCollector's merged "
         "registry).")
_declare("MXT_HEALTH_DIVERGENCE", float, 0.5,
         "Fleet skew-watch divergence threshold: a member grad-norm "
         "fingerprint differing from the fleet median by more than "
         "this relative fraction reads as numeric divergence (data-"
         "parallel replicas should see near-identical global grad "
         "norms).")
_declare("MXT_HEALTH_POSTMORTEM", bool, True,
         "Dump a diagnostics post-mortem on the FIRST health anomaly "
         "of each kind (per monitor) so the flight-recorder tail "
         "around the anomaly is preserved; 0 records events/counters "
         "only.")

_declare("MXT_MEMBERSHIP", bool, True,
         "Elastic membership for the dist kvstore (membership.py): "
         "workers register with the coordinator-side server, heartbeat "
         "on a background thread, and every data frame is fenced by "
         "(worker_id, generation) so a zombie or restarted-but-"
         "unregistered worker can never corrupt server state. 0 "
         "disables registration/fencing (pre-membership behavior).")
_declare("MXT_ELASTIC", bool, False,
         "Route dist_sync reductions through the membership server "
         "(kvstore 'reduce' rendezvous) instead of XLA collectives so "
         "sync mode DEGRADES over survivors when a worker dies instead "
         "of hanging in a collective. Opt-in: the collective path is "
         "faster but cannot drop a dead peer.")
_declare("MXT_MESH_SHAPE", str, None,
         "Comma-separated global mesh shape for no-arg "
         "parallel.make_mesh() calls (e.g. '16,2' for dp×tp, "
         "'2,1,2,2' for the full dp×tp×pp×ep; one -1 wildcard "
         "allowed). Exported per worker by tools/launch.py --mesh so "
         "the same training script scales from 1 host to N without "
         "code changes.")
_declare("MXT_MESH_AXES", str, None,
         "Comma-separated mesh axis names paired with MXT_MESH_SHAPE "
         "(default: 'data,model,pipe,expert' truncated to the shape's "
         "rank; dp/tp/pp/ep spellings are accepted wherever an axis "
         "role is resolved). Set by tools/launch.py --mesh-axes.")
_declare("MXT_ZERO_STAGE", int, None,
         "Default ZeRO weight-update sharding stage (0-3) for "
         "parallel.ShardedTrainStep when the constructor doesn't pass "
         "zero_stage (arXiv:2004.13336: 1 shards optimizer states over "
         "the data axis, 2 adds gradient reduce-scatter + sharded "
         "updates, 3 shards the params themselves FSDP-style). "
         "Exported by tools/launch.py --zero-stage.")
_declare("MXT_HEARTBEAT_INTERVAL", float, 2.0,
         "Seconds between membership heartbeats (membership.py; ref: "
         "ps-lite Van's heartbeat timer).")
_declare("MXT_LIVENESS_TIMEOUT", float, 10.0,
         "Seconds without a heartbeat before the membership reaper "
         "declares a worker dead, fences its generation, and bumps the "
         "membership epoch (lost_workers profiler counter).")
_declare("MXT_BARRIER_TIMEOUT", float, None,
         "Deadline in seconds for KVStore barriers (both the membership "
         "barrier and the jax.distributed sync path). Unset falls back "
         "to MXT_KV_DEADLINE; exceeding it raises KVStoreError instead "
         "of hanging on a peer that will never arrive. Rendezvous "
         "requests give the transport this window plus a small margin "
         "so the server's typed timeout reply beats the client-side "
         "retry (no duplicate waiters).")

_declare("MXT_KV_RETRIES", int, 4,
         "Max retries for a kvstore network op (dist push reduction, "
         "async client request) before raising KVStoreError.")
_declare("MXT_KV_RETRY_BASE", float, 0.05,
         "Base seconds for kvstore retry exponential backoff "
         "(base * 2^(attempt-1), plus jitter).")
_declare("MXT_KV_RETRY_MAX", float, 2.0,
         "Cap in seconds on a single kvstore retry backoff delay.")
_declare("MXT_KV_DEADLINE", float, 30.0,
         "Per-op deadline in seconds for kvstore network ops; exceeding "
         "it raises KVStoreError instead of hanging the worker.")

_declare("MXT_TELEMETRY_JSONL", str, None,
         "Path of the telemetry JSONL event/metric sink (telemetry.py): "
         "step-phase spans, RPC spans, and epoch metric snapshots append "
         "as JSON lines via a buffered writer thread; nd.waitall() and "
         "the estimator's epoch end flush it. Unset disables the sink "
         "(metrics registry stays live either way).")
_declare("MXT_TELEMETRY_PORT", int, None,
         "Serve telemetry.render_prometheus() on 127.0.0.1:<port> "
         "(stdlib HTTP, daemon thread, loopback only). tools/mxt_top.py "
         "tails it for a live console. Unset disables the endpoint; "
         "0 picks a free port (telemetry.http_port() reports it).")

_declare("MXT_PAGE_SIZE", int, 16,
         "Tokens per KV-cache page in the serving stack "
         "(serving/kv_cache.py). The ragged paged attention kernel "
         "streams one page per grid step, so this is also its KV block "
         "size; must be a multiple of 8 (TPU sublane).")
_declare("MXT_SERVING_PAGES", int, 256,
         "KV-cache pool size in pages preallocated per serving engine "
         "(one extra scratch page is always added for masked writes of "
         "inactive batch slots). HBM cost per layer is "
         "2 * pages * page_size * heads * head_dim * itemsize.")
_declare("MXT_SERVING_SLOTS", int, 8,
         "Decode batch slots in the serving engine: the continuous "
         "batcher recomposes requests into this fixed-shape batch every "
         "step, so the decode program compiles once regardless of "
         "traffic (inactive slots are masked, not reshaped away).")

_declare("MXT_FLEET_HEDGE_DELAY", float, None,
         "Hedge delay in seconds for the serving fleet router "
         "(serving/router.py): a dispatched request with no result "
         "after this long is speculatively duplicated onto a second "
         "replica — first completion wins, the loser is cancelled "
         "through the replica's eviction path. Unset derives the delay "
         "per request as half its deadline (or half the router's "
         "slo=); requests with neither never hedge.")
_declare("MXT_FLEET_HEDGE_BUDGET", int, None,
         "Max concurrently-hedged requests fleet-wide: bounds the "
         "extra load a brownout can recruit, so hedging can never "
         "double the fleet's work. 0 disables hedging; unset derives "
         "max(1, fleet slot capacity // 4).")

_declare("MXT_FLEET_PREFILL_THRESHOLD", int, 64,
         "Prompt length (tokens) at which the fleet router dispatches "
         "a request through the disaggregated prefill/decode handoff "
         "(serving/router.py): prefill on a prefill-role replica, KV "
         "pages shipped over the transport, adopted into a decode-role "
         "replica. Shorter prompts route straight to the decode tier; "
         "pools without both roles always dispatch directly.")

_declare("MXT_FLEET_SCRAPE_TIMEOUT", float, 5.0,
         "Per-member transport deadline in seconds for the fleet "
         "telemetry collector's tel_snapshot/tel_spans scrapes "
         "(telemetry_fleet.py): a dead or hung member costs at most "
         "this long and is then marked stale with its last-seen age — "
         "the collector never hangs on a member.")

_declare("MXT_FLEET_SCRAPE_INTERVAL", float, 2.0,
         "Background scrape period in seconds for "
         "telemetry_fleet.FleetCollector.start() — how often the "
         "collector refreshes membership and re-scrapes every member's "
         "registry and trace spans.")

_declare("MXT_AUTOSCALE_INTERVAL", float, 1.0,
         "Control-loop period in seconds for the serving fleet "
         "autoscaler's background thread (serving/autoscaler.py "
         "FleetAutoscaler.start()) — how often the merged fleet page "
         "is re-read and a scale decision considered.")
_declare("MXT_AUTOSCALE_COOLDOWN", float, 5.0,
         "Minimum seconds between autoscaler actuations in the SAME "
         "replica pool (and per attached worker fleet): after an "
         "up/down decision the loop observes only, so a scale-up's "
         "effect lands in the signals before the next decision — the "
         "anti-flap half of the hysteresis pair.")
_declare("MXT_AUTOSCALE_MIN_REPLICAS", int, 1,
         "Serving-replica floor: the autoscaler refuses typed "
         "(AutoscalerError) any decision or scale_to() that would drop "
         "the routable+warming population below this.")
_declare("MXT_AUTOSCALE_MAX_REPLICAS", int, 8,
         "Serving-replica ceiling: scale-up stops here; scale_to() "
         "above it refuses typed.")
_declare("MXT_AUTOSCALE_QUEUE_HIGH", float, 2.0,
         "Scale-up pressure threshold: queued requests (router backlog "
         "+ merged replica admission queues) >= this many per slot of "
         "fleet capacity reads as hot, as does p99 latency above the "
         "SLO.")
_declare("MXT_AUTOSCALE_OCC_LOW", float, 0.3,
         "Scale-down calm threshold: mean routable-replica occupancy "
         "at or below this fraction, with an empty queue and p99 "
         "within SLO, counts one calm tick.")
_declare("MXT_AUTOSCALE_CALM_TICKS", int, 3,
         "Consecutive calm observations required before the "
         "autoscaler shrinks by one replica — the hysteresis half that "
         "keeps a brief lull from draining capacity a flash crowd "
         "would immediately need back.")
_declare("MXT_AUTOSCALE_SLO", float, None,
         "Target p99 routed-request latency in seconds for the "
         "autoscaler's error signal when the FleetRouter has no slo= "
         "of its own. Unset means latency never reads as hot (queue "
         "pressure still scales).")

_declare("MXT_TENANT_QUOTA_REQUESTS", int, None,
         "Default per-tenant cap on OUTSTANDING requests (admitted, "
         "not yet finished) for serving QoS (serving/qos.py) when a "
         "tenant has no explicit TenantSpec. Unset means unlimited.")
_declare("MXT_TENANT_QUOTA_TOKENS", int, None,
         "Default per-tenant cap on outstanding token budget "
         "(prompt + max_new_tokens summed over in-flight requests). "
         "Unset means unlimited.")

_declare("MXT_WATCHDOG_TIMEOUT", float, None,
         "Hang-watchdog stall threshold in seconds (diagnostics.py): a "
         "progress source (engine window retires, KVStore RPC "
         "completions, membership heartbeats, the serving decode loop) "
         "with outstanding work and no counter movement for this long "
         "triggers a stall report (thread stacks + in-flight window "
         "state + flight-recorder tail + post-mortem file). Unset "
         "disables the watchdog; setting it also arms the post-mortem "
         "handlers at import.")
_declare("MXT_WATCHDOG_ACTION", str, "report",
         "What a watchdog stall does: 'report' keeps the process alive "
         "and re-reports every timeout window; 'abort' dumps the "
         "post-mortem then exits with diagnostics.WATCHDOG_EXIT_CODE "
         "(134) so tools/launch.py --respawn or the membership reaper "
         "can respawn the worker — a typed death instead of a silent "
         "hang.")
_declare("MXT_WATCHDOG_INTERVAL", float, None,
         "Watchdog check period in seconds (default: timeout/4, floor "
         "50 ms). Checks read host heartbeat counters only — never a "
         "device value.")
_declare("MXT_POSTMORTEM_DIR", str, ".",
         "Directory where diagnostics post-mortems "
         "(mxt-postmortem-<ts>.json: flight-recorder ring, thread "
         "stacks, window state, HBM ledger, goodput, config + metrics "
         "snapshots) are written on fatal signal, unhandled exception, "
         "watchdog stall, OOM, or demand.")
_declare("MXT_FLIGHT_RECORDER_SIZE", int, 2048,
         "Bounded ring capacity (events) of the diagnostics flight "
         "recorder. Every telemetry event — step spans, RPC spans, "
         "membership/reshard/checkpoint events — lands here; the tail "
         "rides every post-mortem and /debug/flightrecorder.")

_declare("MXT_AG_LEAN_TAPE", bool, False,
         "Skip storing per-node replay state (forward fn + primal "
         "inputs) on the autograd tape. Saves peak memory on very long "
         "eager recordings whose ops' vjp residuals don't already retain "
         "their inputs, at the cost of grad(create_graph=True) raising.")

_declare("MXT_DATA_WORKERS", int, 2,
         "Decode workers per host in the streaming data plane "
         "(data_plane/workers.py) — the ImageRecordIter "
         "preprocess_threads analog, pulling leased shard chunks "
         "instead of a shared cursor.")
_declare("MXT_DATA_BUFFER_BATCHES", int, 8,
         "Bounded decoded-batch buffer per host (the data plane's "
         "backpressure boundary): decode workers block when the "
         "consumer falls this many batches behind instead of growing "
         "host memory; resident host bytes are the "
         "mxt_data_buffer_bytes gauge.")
_declare("MXT_DATA_CHUNK_RECORDS", int, 256,
         "Records per data-plane chunk — the unit of lease, steal, and "
         "batch formation (batches never cross a chunk, so keep this a "
         "multiple of the batch size). Smaller chunks steal/resume at "
         "finer grain; larger chunks read more sequentially.")
_declare("MXT_DATA_STEAL", bool, True,
         "Cross-host work stealing in the data plane: a host whose "
         "lease queue runs dry steals unleased chunks from the slowest "
         "peer (reclaimed dead-host chunks first). 0 pins every chunk "
         "to its original owner (a dead host's tail is then lost until "
         "it rejoins).")

_declare("MXT_EMBEDDING_SERVERS", str, None,
         "Comma-separated host:port list of a running sharded-embedding "
         "server fleet (embedding/). When unset, kvstore 'dist_embedding' "
         "spins MXT_EMBEDDING_LOCAL_SERVERS in-process servers instead.")
_declare("MXT_EMBEDDING_LOCAL_SERVERS", int, 1,
         "Size of the in-process embedding server fleet started by "
         "kvstore 'dist_embedding' when MXT_EMBEDDING_SERVERS is unset.")
_declare("MXT_EMBEDDING_CACHE_ROWS", int, 4096,
         "Hot-row device cache capacity (rows per embedding table) for "
         "the sharded embedding client; 0 disables the cache "
         "(every lookup goes to the fleet).")
_declare("MXT_EMBEDDING_SNAPSHOT_DIR", str, None,
         "Directory where embedding servers persist their shard "
         "(rows + optimizer state, CRC-manifested) and restore it from "
         "on restart.")

_overrides = {}
# bumped by set_default so value caches (e.g. the flash kernel's block
# memo) can notice a config change without re-reading every variable
_change_epoch = 0


def variables():
    return dict(_REGISTRY)


def change_epoch():
    """Monotone counter bumped by every set_default call — cheap staleness
    check for caches built over config values. Env-var mutations cannot be
    observed this way; callers that must honor them re-read via get()."""
    return _change_epoch


def is_set(name):
    """True when the variable has an explicit value (env var or
    set_default override) rather than its declared default — how the
    tuning layer tells 'user pinned this knob' from 'free to tune'."""
    if name not in _REGISTRY:
        raise MXNetError("unknown config variable %r" % (name,))
    return name in os.environ or name in _overrides


def _coerce(var, raw):
    if raw is None:
        return None
    if var.type is bool:
        return str(raw).lower() in ("1", "true", "yes", "on")
    try:
        return var.type(raw)
    except (TypeError, ValueError) as e:
        raise MXNetError("config %s expects %s, got %r"
                         % (var.name, var.type.__name__, raw)) from e


def get(name):
    """Typed value: env var > set_default override > declared default."""
    if name not in _REGISTRY:
        raise MXNetError("unknown config variable %r (declare it in "
                         "mxnet_tpu/config.py)" % (name,))
    var = _REGISTRY[name]
    raw = os.environ.get(name)
    if raw is not None:
        return _coerce(var, raw)
    if name in _overrides:
        return _overrides[name]
    return var.default


def set_default(name, value):
    """Process-level override (below env in precedence)."""
    global _change_epoch
    if name not in _REGISTRY:
        raise MXNetError("unknown config variable %r" % (name,))
    _overrides[name] = _coerce(_REGISTRY[name], value)
    _change_epoch += 1


def describe():
    """Human-readable table of every variable (env_var.md analog)."""
    lines = ["%-32s %-8s %-12s %s" % ("Variable", "Type", "Current",
                                      "Description")]
    for name in sorted(_REGISTRY):
        var = _REGISTRY[name]
        lines.append("%-32s %-8s %-12s %s"
                     % (name, var.type.__name__, get(name), var.doc))
    return "\n".join(lines)


class naive_engine:
    """Context manager: run ops one-by-one without jit — the debugging
    analog of MXNET_ENGINE_TYPE=NaiveEngine (SURVEY §5 race/debug
    posture)."""

    def __enter__(self):
        import jax
        self._ctx = jax.disable_jit()
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)
