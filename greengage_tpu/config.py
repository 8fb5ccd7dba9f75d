"""Engine configuration — the GUC system analog (guc.c / guc_gp.c).

A small typed settings registry with per-session overrides; the Database
facade exposes SET/SHOW. Names loosely mirror the reference's GUCs
(gp_interconnect_queue_depth etc. -> motion capacity slack here).
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Settings:
    # join probe-chain BOUND: the build/probe walks are dynamic-trip
    # while_loops that run only as deep as the worst real chain (2-4 at
    # load 1/3); the bound only caps pathological chains, flagging
    # overflow for the bigger-table retry tier
    hash_num_probes: int = 32
    hash_table_min: int = 256
    hash_table_max: int = 1 << 25
    # dense group-by path: used when the product of group-key domains
    # (dictionary sizes / bool) is at most this (scatter-free aggregation)
    dense_group_limit: int = 512
    # motion (gp_interconnect_queue_depth analog)
    motion_capacity_slack: float = 1.6  # per-destination bucket headroom
    motion_retry_tiers: int = 3         # capacity x4 per retry on overflow
    # pipelined motion (docs/PERF.md "Data movement"): motion_pipeline
    # overlaps the host side of bucketed spill schedules — bucket k+1's
    # staging runs on a background thread while bucket k computes; off =
    # the serial-phase loops (the microbench baseline).
    # motion_pipeline_buckets > 1 additionally splits each compiled
    # redistribute into that many sub-exchanges along the capacity axis
    # (row-order identical to the single all_to_all) so XLA can overlap
    # exchange k+1 with compute on exchange k's rows; 1 = the single
    # monolithic all_to_all (the pre-PR-18 program, byte-identical)
    motion_pipeline: bool = True
    motion_pipeline_buckets: int = 1
    # planner selection (the GUC 'optimizer' analog): on = Cascades-lite
    # memo search (planner/memo.py, the ORCA engine analog); off = the
    # left-deep Selinger DP / greedy order in the binder
    optimizer: bool = True
    explain_verbose: bool = False
    # memory protection (gp_vmem_protect_limit analog): estimated device
    # bytes a single query may allocate; 0 disables the check
    vmem_protect_limit_mb: int = 12288
    # mid-flight enforcement (vmem_tracker.c + redzone_handler.c +
    # runaway_cleaner.c analog): cluster-wide ceiling on the SUM of
    # in-flight statements' compiled estimates; crossing
    # runaway_red_zone x this flags the heaviest statement, which
    # terminates at its next cancellation point (retry-tier or spill-pass
    # boundary). 0 disables cross-statement enforcement.
    vmem_global_limit_mb: int = 0
    runaway_red_zone: float = 0.9
    # feedback-driven cost calibration (planner/feedback.py): reconcile
    # per-node actual rows + measured executable bytes against planner
    # estimates after every execution, and apply the learned per-digest
    # row-scale corrections at plan time (bounded EWMA; a promotion
    # bumps the calibration version so the shape re-plans). Off =
    # estimates stay static (the store still reports via gg checkperf).
    cost_feedback: bool = True
    # hysteresis band around an applied correction: the EWMA candidate
    # must drift by more than this FACTOR before it re-applies (and
    # re-plans the shapes using it) — estimate noise inside the band
    # never invalidates cached plans
    cost_feedback_hysteresis: float = 1.5
    # on a device RESOURCE_EXHAUSTED the statement demotes to the spill
    # path once (the workfile fallback) before surfacing the typed
    # OutOfDeviceMemory; off = fail fast with the forensics dump only
    oom_spill_retry: bool = True
    # synchronous mirror replication after each committed write (the
    # synchronous_standby_names / syncrep gate analog); off = mirrors go
    # stale and are barred from promotion until `gg replicate`
    mirror_sync: bool = True
    # resource queue (resscheduler.c ResLockPortal analog): bound on
    # concurrent mesh statements (0 = unlimited), per-query estimated
    # device memory ceiling, and how long a statement may queue
    resource_queue_active: int = 0
    resource_queue_memory_mb: int = 0
    resource_queue_timeout_s: float = 30.0
    # resource groups: cluster-wide cap on concurrent mesh statements;
    # when it binds, the backoff scheduler picks the next group by
    # weighted consumed chip time (runtime/resgroup.py)
    resource_group_global_active: int = 0
    # storage
    default_compresstype: str = "zlib"
    default_compresslevel: int = 1
    # host data path (docs/PERF.md; the bufmgr/smgr pipeline analog):
    # scan_threads sizes the staging read+decode pool (0 = auto:
    # min(8, cpu count)); 1 disables concurrency entirely.
    scan_threads: int = 0
    # one byte budget for every block cache (decoded blocks, footers, raw
    # chunks, host predicates, deletion masks, staged device inputs) —
    # the shared_buffers analog, LRU-evicted across all of them
    scan_cache_limit_mb: int = 1024
    # spill passes warm the next pass's cold block reads on a background
    # thread while the current pass's jitted program runs
    spill_prefetch: bool = True
    # tiered spill workfile (exec/workfile.py; docs/PERF.md "Data
    # movement"): captured spill passes land in a byte-accounted host-RAM
    # tier; once a statement's retained passes exceed spill_host_limit_mb
    # the coldest passes demote to compressed segment files under
    # spill_dir (default <cluster>/spill when empty) and are promoted
    # back to RAM ahead of the merge schedule. 0 = RAM-only (the
    # pre-tiered behavior: the workfile never touches disk)
    spill_dir: str = ""
    spill_host_limit_mb: int = 512
    # window-partition spill (exec/spill.py spill_window_run): a window
    # whose working set exceeds the admission limit captures its input in
    # chunked passes, then runs the window over disjoint PARTITION BY
    # hash buckets — whole partitions per bucket, exact results. Off =
    # honest admission rejection (the pre-spill behavior)
    window_spill_enabled: bool = True
    # scalar data-path fusion (ops/scalar.py; docs/PERF.md "Scalar
    # data-path fusion"): lower raw-TEXT string-function chains to device
    # byte-window ops (E.RawStrOp) inside the fused programs; off = the
    # legacy per-row host chains (the microbench baseline). Dictionary-LUT
    # and date/numeric device scalars are always on — they have no host
    # fallback to compare against.
    scalar_device_enabled: bool = True
    # sampled-splitter range repartition for ordered global windows
    # (exec/compile.py _c_motion range branch): per-segment sample size
    # feeding the global splitter selection; larger = better balance for
    # skewed keys at a few KB of extra all_gather
    window_range_sample: int = 64
    # read-path self-heal (docs/ROBUSTNESS.md storage failure model): a
    # corrupt/missing block file is repaired from the IN-SYNC standby tree
    # and the read retried once; off = detect-and-quarantine only (the
    # file still quarantines, storage_ok fails, FTS failover takes over)
    storage_autorepair: bool = True
    # multihost control-plane deadlines + liveness (docs/ROBUSTNESS.md;
    # gp_segment_connect_timeout / gp_fts_probe_timeout family): silence
    # past these bounds classifies as WorkerDied instead of a hang
    mh_connect_deadline: float = 60.0   # gang assembly accept + (re)connect
    mh_ready_deadline: float = 120.0    # readiness acks (refresh+plan+verify)
    mh_ack_deadline: float = 600.0      # completion acks (compile+execute)
    mh_heartbeat_interval: float = 2.0  # idle ping/pong cadence; 0 disables
    # statement lifecycle (docs/ROBUSTNESS.md): statement_timeout arms a
    # deadline at statement start; the statement dies at its next
    # cancellation point (boundary-granular — a dispatched XLA program
    # runs to its boundary). 0 disables.
    statement_timeout_s: float = 0.0
    # read-only dispatch retry: after WorkerDied mid-dispatch, how long
    # the coordinator waits for the gang to re-form before serving the
    # statement on the degraded local path instead (writes never retry)
    mh_retry_window_s: float = 1.0
    # N-1 mesh re-formation (docs/ROBUSTNESS.md "Topology re-formation"):
    # on worker death the coordinator rebuilds the gang over the SURVIVORS
    # (mirror-promoted contents served from surviving roots) instead of
    # falling to the single-process degraded path; off = legacy degrade.
    # The deadline bounds how long re-formation waits for survivors to
    # redial the kept listener before adopting whoever arrived.
    mh_reform_enabled: bool = True
    mh_reform_deadline_s: float = 10.0
    # coordinator failover (docs/ROBUSTNESS.md "Coordinator failover"):
    # mh_coordinator_addrs is the ordered "host:port,host:port" list a
    # worker's CoordinatorLost redial walks — first the address it was
    # launched against, then the standby's listener — so a promoted
    # standby adopts the surviving gang without any process restart
    # (empty = redial the launch address only, the legacy behavior).
    # The standby watcher (`gg standby --watch`) pull-syncs the primary's
    # commit tail every standby_watch_interval_s and auto-promotes once
    # the primary's liveness beat has been silent past
    # standby_promote_deadline_s (the gp_fts_probe_timeout analog for the
    # coordinator itself; promotion fences the old primary first, so a
    # paused-not-dead coordinator cannot split-brain).
    mh_coordinator_addrs: str = ""
    standby_promote_deadline_s: float = 15.0
    standby_watch_interval_s: float = 1.0
    # per-table delta manifests (storage/manifest.py): fold the delta
    # backlog into the root snapshot once it reaches this many commits
    # (the checkpoint_segments analog); 0 folds on every commit
    manifest_delta_fold_threshold: int = 64
    # hot-table write scale (storage/manifest.py write-intent path,
    # runtime/ingest.py streaming plane): write_intents_enabled routes
    # autocommit appends through txid-named intent records (same-table
    # appenders commit with zero claim retries; off = the per-table CAS
    # for every write). Stream sessions buffer rows host-side up to
    # ingest_buffer_rows (overflow past an inline flush sheds, typed and
    # retryable), committing micro-batches at ingest_batch_rows rows or
    # ingest_batch_ms milliseconds — the durability watermarks. A stream
    # idle past ingest_stream_idle_s is flushed and closed by the
    # flusher (abandoned-client hygiene); 0 disables the deadline.
    write_intents_enabled: bool = True
    ingest_batch_rows: int = 4096
    ingest_batch_ms: float = 250.0
    ingest_buffer_rows: int = 65536
    ingest_stream_idle_s: float = 300.0
    # plan / executable cache (plancache.c prepared-statement analog;
    # docs/PERF.md "Plan cache"): plan_cache_params hoists plan-safe
    # literals into runtime parameters so one XLA executable serves every
    # value of a query shape (off = classic value-pinned plans);
    # plan_cache_size bounds BOTH the session's bound-plan LRU and the
    # executor's compiled-program LRU (each program entry pins an XLA
    # executable)
    plan_cache_params: bool = True
    plan_cache_size: int = 256
    # vectorized serving (exec/batchserve.py; docs/PERF.md "Vectorized
    # serving"): concurrent SELECTs sharing one literal-stripped statement
    # shape are collected during an admission window and executed as ONE
    # XLA dispatch over their stacked parameter vectors. Off by default —
    # a serving deployment opts in; the single-user path is unchanged.
    # batch_window_ms bounds how long a statement may wait for batch-mates
    # (the window only opens while the serving pipeline is busy — an idle
    # pipeline dispatches immediately, so the window costs latency only
    # when the device is the bottleneck anyway); batch_max_width flushes a
    # window early when it fills, and bounds the stacked width (widths
    # compile per pow2 bucket, so 1..max_width costs log2 compiles)
    batch_serving_enabled: bool = False
    batch_window_ms: float = 2.0
    batch_max_width: int = 16
    # ---- overload armor (docs/ROBUSTNESS.md "Overload protection") ----
    # bounded front end (runtime/server.py): cap on concurrent client
    # connections — excess connects get a typed too_many_connections
    # fast-fail (SQLSTATE 53300 analog) instead of silent thread growth;
    # 0 = unlimited (the embedded/test default behavior stays reachable)
    max_connections: int = 100
    # auth-handshake deadline for remote (TCP) peers: a connect that
    # never completes the challenge-response is closed, so a port-scan
    # or stalled client cannot pin a handler thread forever (0 = off)
    client_auth_deadline_s: float = 10.0
    # idle-read deadline between statements: a connection silent past
    # this is told idle_timeout and closed (0 = off, the default — BI
    # tools hold idle connections legitimately)
    client_idle_timeout_s: float = 0.0
    # maximum request-frame size (one newline-delimited JSON line): an
    # oversized frame is rejected with frame_too_large and the
    # connection closed (the stream cannot be resynced), so a multi-GB
    # line cannot OOM the host
    max_frame_bytes: int = 64 << 20
    # graceful-drain window for SqlServer.stop(): in-flight statements
    # are flagged shutdown and handler threads joined up to this bound
    # before their sockets are force-closed
    server_drain_s: float = 5.0
    # load shedding (runtime/resqueue.py shed_check, shared by the
    # resource queue and resource groups): cap on statements WAITING for
    # an admission slot — at the cap the statement is rejected with the
    # typed, retryable AdmissionShed (SQLSTATE 53300 analog) instead of
    # queueing forever; 0 = queue forever (legacy). Rejection ramps in
    # probabilistically from admission_shed_ramp x cap so the approach
    # to the cap sheds gradually, not as a cliff.
    admission_queue_limit: int = 0
    admission_shed_ramp: float = 0.75
    # serving-pipeline cap (exec/batchserve.py): members allowed to wait
    # across open admission windows; past it, new members shed to the
    # classic serial path (which the admission queue bounds) instead of
    # accumulating unboundedly while the device is busy. 0 = uncapped.
    batch_queue_limit: int = 512
    # memory-pressure brownout (runtime/overload.py): on sustained HBM
    # pressure (watermark fraction or an OOM streak) the engine enters a
    # typed brownout — block-cache budget x brownout_cache_factor, batch
    # serving disabled, admission ceiling x brownout_vmem_factor so new
    # statements prefer the spill tier — and exits only after every
    # signal stays clear for brownout_exit_s (hysteresis; the watermark
    # bar also drops to brownout_exit_pct while browned out)
    brownout_enabled: bool = True
    brownout_enter_pct: float = 0.92
    brownout_exit_pct: float = 0.80
    brownout_oom_events: int = 3
    brownout_window_s: float = 30.0
    brownout_exit_s: float = 5.0
    brownout_cache_factor: float = 0.5
    brownout_vmem_factor: float = 0.5
    # plan-invariant validation (analysis/plancheck.py; the cdbmutate
    # checkPlan-before-dispatch analog): walk every planned statement and
    # raise a typed PlanInvariantError on Motion-placement / locality /
    # prune-shape violations BEFORE compile or dispatch. The walk is
    # O(plan nodes) of host attribute checks — noise next to planning —
    # so it defaults on everywhere, not just in tests
    plan_validate: bool = True
    # logging (log_statement / log_min_duration_statement analog): every
    # statement + errors land in <cluster>/log CSV files
    log_statement: bool = True
    # observability (docs/OBSERVABILITY.md; the gpperfmon analog):
    # trace_enabled records per-phase spans for every statement into the
    # bounded completed-trace ring (`gg trace <id>` exports Chrome
    # trace_event JSON); log_min_duration_ms additionally writes a
    # slow_statement log row (plan digest + trace id) and dumps the trace
    # JSON beside the CSV logs for any statement at/above the threshold
    # (-1 disables, 0 logs every statement)
    trace_enabled: bool = True
    trace_ring_size: int = 64
    log_min_duration_ms: float = -1.0
    # continuous archiving (archive_mode/archive_command analog): after
    # each committed write, ship the new manifest version + its new
    # segment files to archive_dir (storage/archive.py); `gg restore-pitr`
    # rebuilds any archived version
    archive_mode: bool = False
    archive_dir: str = ""

    _overrides: dict = field(default_factory=dict)

    def set(self, name: str, value) -> None:
        if not hasattr(self, name) or name.startswith("_"):
            raise ValueError(f'unrecognized configuration parameter "{name}"')
        cur = getattr(self, name)
        if isinstance(cur, bool):
            value = str(value).lower() in ("1", "true", "on", "yes")
        elif isinstance(cur, int):
            value = int(value)
        elif isinstance(cur, float):
            value = float(value)
        setattr(self, name, value)

    def show(self, name: str):
        if not hasattr(self, name) or name.startswith("_"):
            raise ValueError(f'unrecognized configuration parameter "{name}"')
        return getattr(self, name)
