"""Configuration system.

Mirrors the contracts of the reference's string-keyed Configuration
(flink-core/.../configuration/Configuration.java:43) with typed ConfigOption
(ConfigOptions.java:53), re-done as plain Python. Loads ``flink-tpu-conf.yaml``
(a flat ``key: value`` file, like GlobalConfiguration.java:36 does for
flink-conf.yaml) without requiring a YAML dependency.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Generic, Optional, TypeVar

T = TypeVar("T")


@dataclass(frozen=True)
class ConfigOption(Generic[T]):
    key: str
    default: Optional[T] = None
    description: str = ""
    # declared value type; inferred from the default when omitted. An
    # option whose default is None (e.g. checkpoint.dir) can still
    # declare one, so conf-file strings coerce — and mis-parse loudly —
    # regardless of whether a default exists.
    type: Optional[type] = None

    def with_default(self, default: T) -> "ConfigOption[T]":
        return ConfigOption(self.key, default, self.description, self.type)

    def value_type(self) -> Optional[type]:
        if self.type is not None:
            return self.type
        if self.default is not None:
            return builtins_type(self.default)
        return None


def builtins_type(v) -> type:
    # bool before int: isinstance(True, int) holds, and a bool option
    # must parse "false" as False, not int("false")
    return bool if isinstance(v, bool) else type(v)


_TRUE = ("true", "1", "yes", "on")
_FALSE = ("false", "0", "no", "off")


def coerce_value(key: str, v: str, t: type):
    """Parse a conf-file string as declared type ``t``; failures name
    the config key (an anonymous ``ValueError: invalid literal`` from
    deep inside a job setup is undebuggable) and unrecognized boolean
    strings are REJECTED rather than silently mapped to False."""
    s = v.strip()
    if t is bool:
        low = s.lower()
        if low in _TRUE:
            return True
        if low in _FALSE:
            return False
        raise ValueError(
            f"config {key!r}: {v!r} is not a boolean "
            f"(expected one of {_TRUE + _FALSE})"
        )
    try:
        return t(s)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"config {key!r}: cannot parse {v!r} as {t.__name__}"
        ) from e


class Configuration:
    """String-keyed config map with typed accessors."""

    def __init__(self, data: Optional[dict] = None):
        self._data: dict[str, Any] = dict(data or {})

    # -- generic --------------------------------------------------------
    def set(self, key, value) -> "Configuration":
        self._data[key.key if isinstance(key, ConfigOption) else key] = value
        return self

    def get(self, option: ConfigOption, default=None):
        if option.key in self._data:
            v = self._data[option.key]
            # conf-file values arrive as STRINGS (the flat-yaml loader
            # stores text); coerce to the option's DECLARED type — not
            # the default's presence — so `parallelism.default: 4`
            # never leaks '4' into arithmetic and a default-None option
            # still parses (and mis-parses loudly, with the key named)
            t = option.value_type()
            if t is None and default is not None:
                t = builtins_type(default)
            if isinstance(v, str) and t is not None and t is not str:
                return coerce_value(option.key, v, t)
            return v
        return option.default if default is None else default

    def contains(self, option: ConfigOption) -> bool:
        return option.key in self._data

    # -- typed ----------------------------------------------------------
    def get_int(self, key: str, default: int = 0) -> int:
        return int(self._data.get(key, default))

    def get_float(self, key: str, default: float = 0.0) -> float:
        return float(self._data.get(key, default))

    def get_bool(self, key: str, default: bool = False) -> bool:
        v = self._data.get(key, default)
        if isinstance(v, str):
            return v.strip().lower() in ("true", "1", "yes")
        return bool(v)

    def get_str(self, key: str, default: str = "") -> str:
        return str(self._data.get(key, default))

    def to_dict(self) -> dict:
        return dict(self._data)

    def merge(self, other: "Configuration") -> "Configuration":
        out = Configuration(self._data)
        out._data.update(other._data)
        return out

    def __repr__(self):
        return f"Configuration({self._data!r})"


def load_global_configuration(conf_dir: Optional[str] = None) -> Configuration:
    """Load flink-tpu-conf.yaml from conf_dir (or $FLINK_TPU_CONF_DIR).

    Parses the flat `key: value` subset of YAML (comments with #), matching
    how the reference's GlobalConfiguration treats flink-conf.yaml.
    """
    conf_dir = conf_dir or os.environ.get("FLINK_TPU_CONF_DIR", "")
    cfg = Configuration()
    path = os.path.join(conf_dir, "flink-tpu-conf.yaml") if conf_dir else None
    if path and os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if not line or ":" not in line:
                    continue
                k, v = line.split(":", 1)
                cfg.set(k.strip(), v.strip())
    return cfg


class CoreOptions:
    """Registry of well-known options (ref ConfigConstants.java:29 role)."""

    DEFAULT_PARALLELISM = ConfigOption("parallelism.default", 1)
    MAX_PARALLELISM = ConfigOption("parallelism.max", 128)
    BATCH_SIZE = ConfigOption("execution.micro-batch-size", 8192)
    STATE_SLOTS_PER_SHARD = ConfigOption("state.backend.device.slots-per-shard", 1 << 16)
    STATE_PROBE_LENGTH = ConfigOption("state.backend.device.probe-length", 16)
    CHECKPOINT_INTERVAL_STEPS = ConfigOption("checkpoint.interval-steps", 0)
    CHECKPOINT_DIR = ConfigOption("checkpoint.dir", None, type=str)
    # snapshot strategy (flink_tpu/checkpointing, ref incremental RocksDB
    # checkpoints + asynchronous snapshots): "full" writes self-contained
    # snapshots, "incremental" writes delta checkpoints covering only the
    # dirty key groups, chained to a periodic full base via manifest.json
    CHECKPOINT_MODE = ConfigOption(
        "checkpoint.mode", "full",
        "full | incremental (changelog delta + manifest chain)")
    # serialize + write on the background materializer thread; the step
    # loop blocks only for the staging fetch. Defaults on for incremental.
    CHECKPOINT_ASYNC = ConfigOption(
        "checkpoint.async", False,
        "materialize checkpoints on a background thread")
    CHECKPOINT_RETAIN = ConfigOption(
        "checkpoint.retain", 2, "retained checkpoints (chain-closure aware)")
    CHECKPOINT_COMPACT_EVERY = ConfigOption(
        "checkpoint.compact-every", 8,
        "write a fresh full base after this many chained checkpoints")
    CHECKPOINT_STAGING_SLOTS = ConfigOption(
        "checkpoint.staging-slots", 2,
        "host staging buffers in flight (double-buffered by default)")
    # -- task-local snapshot cache (checkpointing/local.py, ref Flink
    # task-local recovery; docs/fault-tolerance.md) ---------------------
    CHECKPOINT_LOCAL_ENABLED = ConfigOption(
        "checkpoint.local.enabled", False,
        "mirror every published checkpoint into a host-local cache with "
        "per-blob checksums; restore prefers the verified local copy "
        "per chain member and falls back to primary on miss/corruption")
    CHECKPOINT_LOCAL_DIR = ConfigOption(
        "checkpoint.local.dir", None, type=str,
        description="task-local cache directory (node-local disk in "
        "production); default: a '<checkpoint.dir>-local' sibling")
    # -- recovery fast path (docs/fault-tolerance.md) -------------------
    RECOVERY_WARM_RESTART = ConfigOption(
        "recovery.warm-restart", True,
        "classify failures at the restart boundary and recover "
        "TRANSIENT host-side ones (watchdog trip, checkpoint budget "
        "exhaustion, DCN peer stall, ingest-thread death) in-process: "
        "live jitted kernels are reused (no recompile) and only the "
        "key groups dirty since the restored cut are re-staged when "
        "the cut's fire horizon still matches; off = every restart "
        "takes the full restore path")
    # -- elastic recovery (runtime/elastic.py; docs/fault-tolerance.md) -
    RECOVERY_ELASTIC = ConfigOption(
        "recovery.elastic", True,
        "re-plan the job at reduced parallelism when a mesh shard's "
        "device is lost (DeviceLostError / detected device loss): "
        "re-slice key-group ranges over the survivors, rebuild the "
        "compiled step family, rescaled-restore the last durable cut, "
        "and resume exactly-once in degraded mode; off = device loss "
        "takes the ordinary full-restore path at the original "
        "parallelism (which on real hardware fails until the device "
        "returns)")
    RECOVERY_MIN_SHARDS = ConfigOption(
        "recovery.min-shards", 1,
        "fewest surviving shards the elastic re-plan may degrade to; "
        "losing capacity below this floor FAILS the job instead of "
        "re-planning (capacity-critical jobs set it near the planned "
        "parallelism)")
    # -- pipelined ingest (runtime/ingest.py; docs/performance.md) ------
    # prep-half prefetch thread: poll + encode of batch k+1 overlaps the
    # device step of batch k. Checkpoint-compatible since the epoch-
    # tagged applied-offset cut — "auto" is on for every windowed stage.
    PIPELINE_PREFETCH = ConfigOption(
        "pipeline.prefetch", "auto",
        "auto | on | off — overlap source poll + host encode with device "
        "compute (off is the fully-serial escape hatch)")
    PIPELINE_MAX_INFLIGHT = ConfigOption(
        "pipeline.max-inflight-steps", 4,
        "bound on dispatched-but-unfinished update steps (caps the fire "
        "wait behind the device backlog)")
    PIPELINE_DEVICE_STAGING = ConfigOption(
        "pipeline.device-staging", "auto",
        "auto | on | off — pad + jax.device_put batches on the ingest "
        "thread (route-aware sharding) so the H2D transfer of batch k+1 "
        "overlaps the step of batch k; auto follows pipeline.prefetch")
    PIPELINE_STAGING_RING = ConfigOption(
        "pipeline.staging-ring-depth", 2,
        "preallocated host padding buffers recycled by the ingest "
        "thread (2 = double-buffered)")
    PIPELINE_PREFETCH_DEPTH = ConfigOption(
        "pipeline.prefetch-depth", 2,
        "prepped batches the ingest queue holds ahead of the step loop")
    # -- dispatch fusion + pre-combine (docs/performance.md) ------------
    PIPELINE_STEPS_PER_DISPATCH = ConfigOption(
        "pipeline.steps-per-dispatch", 1,
        "K staged micro-batches fused into ONE jitted lax.scan megastep "
        "dispatch; divides the fixed per-dispatch cost (Python, tracing, "
        "and the dispatch itself) by K at the cost of K-batch "
        "fire/checkpoint granularity. 1 = unfused (bit-identical "
        "single-step dispatch)")
    UPDATE_PRECOMBINE = ConfigOption(
        "pipeline.update-precombine", "auto",
        "auto | on | off — collapse duplicate (slot, pane) scatter keys "
        "with one shared sort + segmented scan before the state scatter "
        "(built-in reducers; duplicate scatter indices serialize on "
        "TPU). auto enables it on accelerator backends and keeps the "
        "CPU path unsorted (XLA's CPU sort costs more than the CPU "
        "scatter it saves — measured in device_update_ceiling)")
    PIPELINE_FUSED_FIRE = ConfigOption(
        "pipeline.fused-fire", "auto",
        "auto | on | off — fold the fire sweep into the K-fused megastep "
        "scan (the resident pipeline, ISSUE 7): a pane-boundary crossing "
        "inside a K-group fires WITHIN the scan instead of breaking the "
        "group and paying a separate fire dispatch; fire payloads "
        "surface as lagged megastep outputs. auto = on whenever "
        "steps-per-dispatch > 1; off keeps the split-dispatch path "
        "(which always remains the fallback for partial groups and the "
        "DCN lockstep plane)")
    PIPELINE_RESIDENT_LOOP = ConfigOption(
        "pipeline.resident-loop", "auto",
        "auto | on | while | off — the device-resident steady-state "
        "loop (ISSUE 12): the prefetch thread publishes staged batches "
        "into an HBM batch ring and the step loop dispatches ONE jitted "
        "drain over every ready slot (fused update+fire per slot, "
        "count-gated), so steady state costs one host round trip per "
        "ring drain instead of one per megastep. Requires prefetch + "
        "device staging + fused fire; exactly-once cuts move to "
        "ring-drain boundaries. auto = on whenever the fused-fire "
        "resident pipeline is active on a single-controller topology. "
        "while (ISSUE 20) swaps the count-gated scan for an early-exit "
        "lax.while_loop whose condition re-reads the ring's HBM publish "
        "cursor, so a batch published mid-drain retires in the SAME "
        "dispatch (bounded by pipeline.while-drain.max-slots); CPU "
        "backends keep the scan drain (no-aliasing platform gate — see "
        "pipeline.while-drain.cpu-override). DCN coordinator jobs "
        "compose per-host: on/while run the host-local resident drain "
        "between lockstep exchange boundaries (ISSUE 20b)")
    PIPELINE_RING_DEPTH = ConfigOption(
        "pipeline.ring-depth", 16,
        "HBM slots in the device batch ring (pipeline.resident-loop): "
        "bounds device-resident batches AND the max slots one drain "
        "dispatch consumes — deeper rings amortize the host round trip "
        "further but coarsen fire/checkpoint latency and HBM residency")
    PIPELINE_WHILE_DRAIN_MAX_SLOTS = ConfigOption(
        "pipeline.while-drain.max-slots", 0,
        "per-dispatch slot bound for pipeline.resident-loop=while: the "
        "while-drain retires at most this many ring slots in one "
        "dispatch regardless of how many publishes land mid-drain, so "
        "the exactly-once cut, the watchdog deadline (armed at the "
        "BOUND, not the observed fill), and the flight-recorder payload "
        "[n_shards, max_slots, 9] stay well-defined. 0 (default) sizes "
        "it to 2 x pipeline.ring-depth, never below ring-depth")
    PIPELINE_WHILE_DRAIN_CPU_OVERRIDE = ConfigOption(
        "pipeline.while-drain.cpu-override", "off",
        "on | off — run the while-drain kernel on CPU backends despite "
        "the platform gate (CPU buffer donation does not alias, so the "
        "cursor freezes at its dispatch snapshot and the while drain "
        "degrades to exactly the scan drain's count gating). Test/bench "
        "escape hatch; production CPU runs keep the scan drain")
    PIPELINE_DATA_PARALLEL = ConfigOption(
        "pipeline.data-parallel", "auto",
        "auto | on | off — mesh-resident data parallelism (ISSUE 13): "
        "each chip owns a contiguous key-group slice, the prefetch "
        "thread routes records to the owning shard off-loop and "
        "publishes into that shard's slice of a sharded device batch "
        "ring, and ONE shard_map'd drain dispatch advances every "
        "shard's ring concurrently with zero cross-chip collectives on "
        "the keyed hot path (fires pack per-shard and merge host-side "
        "on the lagged consume path). Requires the resident loop; "
        "batches whose per-shard skew overflows the ring slice fall "
        "back to the replicated mask route for that batch only. auto = "
        "on whenever the resident loop is active on a multi-chip mesh")
    PIPELINE_SHARD_CAPACITY_FACTOR = ConfigOption(
        "pipeline.shard-capacity-factor", 2.0,
        "per-shard ring-slice rows as a multiple of the uniform share "
        "B/n_shards (pipeline.data-parallel): headroom for key-group "
        "skew before a batch falls back to the replicated route — "
        "larger tolerates hotter shards at the cost of HBM and padded "
        "drain work")
    PIPELINE_STAGES_EXCHANGE_LANES = ConfigOption(
        "pipeline.stages.exchange-lanes", 1024,
        "chained stage graphs (runtime/stages.py, ISSUE 16): lanes of "
        "the on-device inter-stage exchange — the packed fire rows one "
        "drain slot may hand from stage N to stage N+1. Sized above "
        "fires-per-step x the per-fire key population the upstream "
        "stage can emit; overrun counts into the DOWNSTREAM stage's "
        "dropped_capacity (strict capacity surfaces it)")
    PIPELINE_STAGES_MAX_STAGES = ConfigOption(
        "pipeline.stages.max-stages", 4,
        "chained stage graphs: maximum keyed windowed stages one job "
        "may chain through the resident drain. Each stage adds its own "
        "table+ring state and per-slot update+fire work to the ONE "
        "drain dispatch; the cap keeps a pathological deep chain a "
        "loud setup error instead of an HBM surprise")
    STATE_PACKED_PLANES = ConfigOption(
        "state.packed-planes", "auto",
        "auto | on | off — store the touched (fire-eligibility) bits as "
        "a trailing column of the pane accumulator so the update issues "
        "ONE scatter over wider lanes and ring-reset/purge sweeps clear "
        "one plane instead of two (built-in reducers with default "
        "neutrals only). auto enables it on accelerator backends where "
        "scatter passes dominate; CPU keeps split planes (the wider "
        "sweep costs more than the scatter it saves — measured in "
        "device_update_ceiling)")
    # tiered key-group state (round 18): HBM-resident hot set over the
    # host spill tier, watermark-driven prefetch (docs/state-tiers.md)
    STATE_TIERS_RESIDENT_KEY_GROUPS = ConfigOption(
        "state.tiers.resident-key-groups", 0,
        "key-groups kept HBM-resident per shard (0 = tiering off, every "
        "group resident). Cold groups demote to the host spill tier and "
        "promote back ahead of their predicted next fire; a batch "
        "routing into a non-resident group rides the overflow ring for "
        "that batch only (never lossy, counted in tier_faults). "
        "Requires a spill-tier-eligible stage (builtin float32 reduce, "
        "allowed lateness 0, no chained stages) with an overflow ring")
    STATE_TIERS_PREFETCH_AHEAD_PANES = ConfigOption(
        "state.tiers.prefetch-ahead-panes", 2,
        "promote a cold key-group once its earliest pending pane is "
        "within this many panes of the watermark — the window fire it "
        "predicts then comes off the device instead of a host merge")
    STATE_TIERS_MIN_DWELL_CYCLES = ConfigOption(
        "state.tiers.min-dwell-cycles", 4,
        "poll cycles a key-group must stay in its tier before the "
        "ranker may flip it again (hysteresis against promote/demote "
        "thrash; an imminent-fire promote overrides it)")
    STATE_TIERS_MAX_SWAPS_PER_CYCLE = ConfigOption(
        "state.tiers.max-swaps-per-cycle", 0,
        "cap on tier promote+demote moves one poll cycle may splice "
        "(0 = unlimited); a working-set shift bigger than the cap "
        "carries the remainder to the next cycle instead of stalling "
        "the step loop behind one giant swap burst")
    RESTART_STRATEGY = ConfigOption("restart-strategy", "none")
    RESTART_ATTEMPTS = ConfigOption("restart-strategy.fixed-delay.attempts", 3)
    RESTART_DELAY_S = ConfigOption("restart-strategy.fixed-delay.delay", 0.0)
    RESTART_FAILURE_RATE_MAX = ConfigOption(
        "restart-strategy.failure-rate.max-failures", 3)
    RESTART_FAILURE_RATE_INTERVAL = ConfigOption(
        "restart-strategy.failure-rate.interval", 60.0)
    RESTART_FAILURE_RATE_DELAY = ConfigOption(
        "restart-strategy.failure-rate.delay", 0.0)
    # exponential-backoff restart strategy (ref RestartStrategies.
    # exponentialDelayRestart): delay doubles per consecutive failure up
    # to max-delay, a quiet period resets it, jitter decorrelates
    # restart storms across jobs. Restarts are unbounded like the
    # reference — the growing delay is the budget.
    RESTART_EXP_INITIAL_DELAY = ConfigOption(
        "restart-strategy.exponential-backoff.initial-delay", 1.0,
        "seconds before the first restart attempt")
    RESTART_EXP_MAX_DELAY = ConfigOption(
        "restart-strategy.exponential-backoff.max-delay", 60.0,
        "ceiling (s) the growing delay never exceeds")
    RESTART_EXP_MULTIPLIER = ConfigOption(
        "restart-strategy.exponential-backoff.multiplier", 2.0,
        "delay growth factor per consecutive failure")
    RESTART_EXP_JITTER = ConfigOption(
        "restart-strategy.exponential-backoff.jitter", 0.1,
        "+- fraction of the delay drawn uniformly at random")
    RESTART_EXP_RESET_AFTER = ConfigOption(
        "restart-strategy.exponential-backoff.reset-after", 3600.0,
        "a failure-free quiet period (s) this long resets the delay "
        "back to initial-delay")
    # -- failure containment (docs/fault-tolerance.md) ------------------
    # checkpoint failure budget (checkpointing/policy.py, ref
    # CheckpointFailureManager): a failed/timed-out checkpoint is
    # aborted + counted; only exhausting the consecutive-failure budget
    # escalates to the restart strategy
    CHECKPOINT_TOLERABLE_FAILURES = ConfigOption(
        "checkpoint.tolerable-failures", 0,
        "consecutive checkpoint failures tolerated (aborted + counted) "
        "before escalating to the restart strategy; 0 = the first "
        "failure escalates (the pre-budget behavior)")
    CHECKPOINT_TIMEOUT = ConfigOption(
        "checkpoint.timeout", 600.0,
        "seconds an async checkpoint may stay unpublished after its "
        "barrier before it is declared failed (its publish is "
        "cancelled and the failure counts against the budget)")
    CHECKPOINT_MIN_PAUSE = ConfigOption(
        "checkpoint.min-pause", 0.0,
        "minimum pause in seconds between the end of one checkpoint "
        "attempt and the next trigger")
    # step-loop watchdog (runtime/watchdog.py): per-phase deadlines that
    # convert a distributed hang into a clean, attributed job failure
    WATCHDOG_ENABLED = ConfigOption(
        "watchdog.enabled", True,
        "supervise step-loop phases; a phase overrunning its deadline "
        "raises an attributed WatchdogError in the step loop")
    WATCHDOG_INTERVAL = ConfigOption(
        "watchdog.interval", 1.0, "watchdog check period in seconds")
    WATCHDOG_SOURCE_TIMEOUT = ConfigOption(
        "watchdog.source-timeout", 0.0,
        "deadline (s) on the ingest wait per cycle; 0 disables — a "
        "legitimate source may idle indefinitely")
    WATCHDOG_FIRE_TIMEOUT = ConfigOption(
        "watchdog.fire-timeout", 600.0,
        "deadline (s) on one fire-step dispatch")
    WATCHDOG_FETCH_TIMEOUT = ConfigOption(
        "watchdog.fetch-timeout", 600.0,
        "deadline (s) on the barrier device fetch")
    WATCHDOG_CKPT_SYNC_TIMEOUT = ConfigOption(
        "watchdog.checkpoint-sync-timeout", 600.0,
        "deadline (s) on a checkpoint's synchronous phase")
    WATCHDOG_SLOT_TIMEOUT = ConfigOption(
        "watchdog.slot-timeout", 600.0,
        "deadline (s) on the materializer staging-slot wait")
    WATCHDOG_DRAIN_TIMEOUT = ConfigOption(
        "watchdog.drain-timeout", 120.0,
        "PER-SLOT deadline (s) on one resident ring-drain dispatch "
        "(pipeline.resident-loop); armed scaled by the slot count the "
        "drain consumes, so deep drains get proportionally more time. "
        "0 disables")
    WATCHDOG_RESTORE_TIMEOUT = ConfigOption(
        "watchdog.restore-timeout", 900.0,
        "deadline (s) on a whole checkpoint restore; the step-loop "
        "phase deadlines are suspended while a restore runs, so a "
        "legitimately long cold restore cannot trip a steady-state "
        "deadline mid-recovery. 0 disables")
    # -- observability (docs/observability.md) --------------------------
    # step-loop span tracing: bounded ring of phase spans exported as
    # Chrome-trace JSON via /jobs/<jid>/traces (metrics/tracing.py)
    TRACING = ConfigOption(
        "observability.tracing", False,
        "record step-loop phase spans (off by default; negligible when "
        "sampled)")
    TRACE_SAMPLE_EVERY = ConfigOption(
        "observability.trace-sample-every", 1,
        "record spans for every N-th poll cycle only")
    TRACE_BUFFER_SPANS = ConfigOption(
        "observability.trace-buffer-spans", 65536,
        "span ring-buffer capacity (old spans fall off)")
    TRACE_DUMP = ConfigOption(
        "observability.trace-dump", "",
        "write the Chrome-trace JSON to this file when the job ends "
        "(empty = don't)")
    KG_STATS = ConfigOption(
        "observability.kg-stats", None,
        "enable key-group skew telemetry (per-batch fill scatter in the "
        "compiled step + the occupancy kernel at fire boundaries); "
        "defaults to whatever observability.tracing is — off means the "
        "steps compile without any telemetry work")
    KG_STATS_INTERVAL_MS = ConfigOption(
        "observability.kg-stats-interval-ms", 1000,
        "min interval between per-key-group occupancy kernel runs "
        "(refreshed at fire boundaries)")
    DRAIN_STATS = ConfigOption(
        "observability.drain-stats", None,
        "enable the drain-interior flight recorder (per-slot x per-shard "
        "counters stacked inside the resident/sharded ring-drain scan, "
        "unpacked lagged into occupancy/duty-cycle/latency telemetry); "
        "defaults to whatever observability.tracing is — off means the "
        "drain kernels compile without any telemetry work (ledger-"
        "verified byte-identical)")
    DRAIN_STATS_EVERY = ConfigOption(
        "observability.drain-stats-every", 8,
        "fetch the drain-stats payload to the host every N-th drain "
        "dispatch only (the device computes it every drain when the "
        "recorder is compiled in; duty-cycle/occupancy EWMAs update on "
        "every drain regardless). 1 = every drain")
    COMPILE_COST = ConfigOption(
        "observability.compile-cost", False,
        "record XLA cost_analysis (FLOPs/bytes) of the update step at "
        "warmup — costs one extra trace+compile")
    KG_HEAT_ALPHA = ConfigOption(
        "observability.kg-heat-alpha", 0.05,
        "EWMA smoothing factor for the per-key-group heat series the "
        "flight recorder folds the sampled kg-fill counters into "
        "(higher = faster reaction, noisier heat); needs "
        "observability.kg-stats")
    DOCTOR = ConfigOption(
        "observability.doctor", True,
        "enable the pipeline doctor (metrics/doctor.py): a pure "
        "host-side rule engine joining the telemetry planes into "
        "ranked findings with evidence + config remedies, served at "
        "/jobs/<jid>/doctor and `python -m flink_tpu.doctor`")
    DOCTOR_STARVED_THRESHOLD = ConfigOption(
        "observability.doctor.starved-threshold", 0.5,
        "ring-starved EWMA fraction above which the doctor reports a "
        "ring-starved finding (publish side cannot keep the drain fed)")
    DOCTOR_SATURATED_THRESHOLD = ConfigOption(
        "observability.doctor.saturated-threshold", 0.9,
        "drain duty-cycle EWMA above which the doctor reports a "
        "device-saturated finding (every drain retires a full ring)")
    DOCTOR_EDGE_UTILIZATION_THRESHOLD = ConfigOption(
        "observability.doctor.edge-utilization-threshold", 0.8,
        "peak inter-stage edge demand / pipeline.stages.exchange-lanes "
        "ratio above which the doctor warns the edge is near overflow")
    DOCTOR_KG_SKEW_THRESHOLD = ConfigOption(
        "observability.doctor.kg-skew-threshold", 4.0,
        "key-group heat max/mean ratio above which the doctor flags a "
        "shard re-slice candidate")
    DOCTOR_TIER_CHURN_THRESHOLD = ConfigOption(
        "observability.doctor.tier-churn-threshold", 0.5,
        "tier swaps (promotes+demotes) per resident drain above which "
        "the doctor reports tier-thrash (the residency budget is "
        "fighting the working set)")
    DOCTOR_TIER_MISS_THRESHOLD = ConfigOption(
        "observability.doctor.tier-miss-threshold", 0.5,
        "prefetch-miss fraction (misses / (hits+misses)) above which "
        "the doctor reports tier-thrash — promotions arrive after the "
        "traffic they predicted")
    DOCTOR_RECOMPILE_THRESHOLD = ConfigOption(
        "observability.doctor.recompile-threshold", 8,
        "steady-state XLA compiles beyond which the doctor reports a "
        "recompile storm (steady state should dispatch pre-compiled "
        "steps only)")
    # -- self-tuning runtime controller (runtime/controller.py,
    # docs/self-tuning.md): closed loop over the doctor's findings +
    # the raw regime/heat planes, serviced at the poll-cycle seam ------
    CONTROLLER_ENABLED = ConfigOption(
        "controller.enabled", False,
        "enable the self-tuning RuntimeController: bounded hill-climb "
        "over the declared hot knobs keyed on the observed regime, "
        "plus live heat-balanced key-group rebalancing through the "
        "savepoint-cut rescale. Off (the default) constructs nothing "
        "and adds zero work to any path")
    CONTROLLER_INTERVAL_CYCLES = ConfigOption(
        "controller.interval-cycles", 16,
        "poll cycles between controller decisions; each decision "
        "applies at most one knob move or one rebalance, so the "
        "interval is also the minimum spacing between actuations")
    CONTROLLER_REVERT_THRESHOLD = ConfigOption(
        "controller.revert-threshold", 0.05,
        "fractional worsening of the tracked metric (events/s) within "
        "the probation window that auto-reverts a knob move; the "
        "reverted (knob, direction) then sits out a cooldown")
    CONTROLLER_PROBATION_CYCLES = ConfigOption(
        "controller.probation-cycles", 16,
        "poll cycles a knob move stays on probation: the controller "
        "compares the tracked metric before vs after and reverts past "
        "controller.revert-threshold; no new move starts meanwhile")
    CONTROLLER_COOLDOWN_CYCLES = ConfigOption(
        "controller.cooldown-cycles", 64,
        "poll cycles a reverted (knob, direction) pair is barred from "
        "being retried (keeps the hill-climb from oscillating on a "
        "knob the workload has already voted down)")
    CONTROLLER_REBALANCE_THRESHOLD = ConfigOption(
        "controller.rebalance-threshold", 4.0,
        "per-shard key-group heat skew (hottest shard / mean shard "
        "heat) above which the controller considers a live "
        "heat-balanced re-slice of the shard ranges")
    CONTROLLER_MIN_REBALANCE_INTERVAL = ConfigOption(
        "controller.min-rebalance-interval", 30.0,
        "seconds between live rebalances: each one is a savepoint-cut "
        "rescale (flush + snapshot + re-plan + restore), so the rate "
        "limit bounds how much of the job's time rebalancing may eat")
    CONTROLLER_MIN_GAIN = ConfigOption(
        "controller.min-gain", 1.2,
        "predicted imbalance improvement (current hottest-shard heat / "
        "rebalanced hottest-shard heat) a re-slice must clear before "
        "the controller pays for a live rescale; gains under it are "
        "skipped and ledgered as such")
    # -- state backend / keying (docs/performance.md) -------------------
    # The keys below predate the config-hygiene lint (ISSUE 9): they
    # were read as bare literals across the executor; declaring them
    # here is what gives them strict coercion, a single default, and a
    # docs anchor.
    STATE_LAYOUT = ConfigOption(
        "state.backend.layout", "auto",
        "auto | hash | direct — slot layout of the device state table; "
        "direct (slot == key) skips probing for bounded non-negative "
        "int keys, auto picks per job")
    STATE_OVERFLOW_RING = ConfigOption(
        "state.backend.overflow-ring", -1,
        "overflow-ring rows per shard for spillable reduces; -1 = "
        "auto-size from the monitoring lag, 0 disables the ring")
    STATE_STAGE_PROBE_LEN = ConfigOption(
        "state.probe-len", 16,
        "open-addressing probe length of a keyed stage's slot table "
        "(the per-stage override of "
        "state.backend.device.probe-length)")
    STATE_STRICT_CAPACITY = ConfigOption(
        "state.backend.strict-capacity", True,
        "fail the job when records would be dropped (capacity "
        "overflow) rather than tolerate loss")
    KEYS_REVERSE_MAP = ConfigOption(
        "keys.reverse-map", True,
        "keep the host-side hash->original-key reverse map so fired "
        "windows surface user keys; off saves host memory when sinks "
        "only need hashes")
    # -- mesh exchange route (docs/performance.md) ----------------------
    EXCHANGE_MODE = ConfigOption(
        "exchange.mode", "auto",
        "auto | all_to_all | mask — how records reach their owning "
        "shard: per-batch adaptive all_to_all (auto), always exchange, "
        "or always replicate-and-mask")
    EXCHANGE_CAPACITY_FACTOR = ConfigOption(
        "exchange.capacity-factor", 2.0,
        "per-shard exchange bucket headroom over the balanced share "
        "(hash skew beyond it falls back / counts dropped_capacity)")
    # -- windowing ------------------------------------------------------
    WINDOW_RING_PANES = ConfigOption(
        "window.ring-panes", 0,
        "pane ring size override; 0 = auto from window spec + "
        "out-of-orderness")
    WINDOW_FIRES_PER_STEP = ConfigOption(
        "window.fires-per-step", 4,
        "window ends evaluated per fire step")
    # -- cross-host DCN plane (docs/DCN_INGESTION.md) -------------------
    DCN_COORDINATOR = ConfigOption(
        "dcn.coordinator", "",
        "host:port of the jax.distributed coordinator; non-empty "
        "switches the executor to the multi-process DCN plane")
    DCN_NUM_PROCESSES = ConfigOption(
        "dcn.num-processes", 1, "process count of the DCN job")
    DCN_PROCESS_ID = ConfigOption(
        "dcn.process-id", 0, "this process's index in the DCN job")
    DCN_ORIGIN_MS = ConfigOption(
        "dcn.origin-ms", 0,
        "shared time-domain origin (epoch ms) so every process buckets "
        "event time identically")
    DCN_REBALANCE_ADDRS = ConfigOption(
        "dcn.rebalance-addrs", "",
        "comma-separated host:port per process for the work-stealing "
        "rebalance ring side channel")
    DCN_INGEST_PARTITIONER = ConfigOption(
        "dcn.ingest-partitioner", "forward",
        "forward | rebalance — whether each process keeps its source "
        "partition or steals from neighbors over the rebalance ring")
    # -- CEP acceleration -----------------------------------------------
    CEP_DEVICE_ENABLED = ConfigOption(
        "cep.device.enabled", True,
        "compile eligible CEP patterns to the device NFA kernel; off "
        "forces the host interpreter")
    CEP_DEVICE_WITHIN_BUCKETS = ConfigOption(
        "cep.device.within-buckets", 8,
        "time-bucket count for the device NFA's within-window pruning")
    # -- control plane / cluster (docs/DEPLOYMENT.md) -------------------
    CONTROLLER_RPC_PORT = ConfigOption(
        "controller.rpc.port", 6123,
        "control-plane RPC port (the jobmanager.rpc.port analog); "
        "0 = ephemeral")
    CONTROLLER_BIND_HOST = ConfigOption(
        "controller.bind-host", "127.0.0.1", "control-plane bind host")
    HA_DIR = ConfigOption(
        "high-availability.dir", None,
        "file-lock leader-election directory (the ZooKeeper-quorum "
        "analog); unset = standalone", type=str)
    SECURITY_AUTH_TOKEN = ConfigOption(
        "security.auth.token", "",
        "shared-secret token for the control plane + HTTP monitor; "
        "empty = open cluster")
    SECURITY_AUTH_TOKEN_FILE = ConfigOption(
        "security.auth.token-file", "",
        "file to read the shared-secret token from (wins over env)")
    METRICS_REPORTERS = ConfigOption(
        "metrics.reporters", "",
        "comma-separated reporter names; each configures via "
        "metrics.reporter.<name>.* keys")
