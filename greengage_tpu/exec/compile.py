"""Physical compiler: planned tree -> one jitted SPMD program per query.

Where the reference interprets plans tuple-at-a-time per slice process
(ExecutorRun/ExecProcNode, src/backend/executor/execMain.c:1020), we compile
the ENTIRE plan below the top Gather Motion into a single function traced
under shard_map over the segment mesh: scans are padded device arrays,
Motions are collectives (parallel/motion.py), operators are the vectorized
kernels in ops/. XLA fuses across operator boundaries — the slice model
survives logically (Motion = slice boundary) but costs no process hop.

Static-shape policy (SURVEY.md §7 "hard parts"): all capacities derive from
storage manifests + planner estimates; kernels report overflow flags
(hash-table or motion-bucket exhaustion) and the executor re-compiles at the
next size tier — the spill/flow-control analog.
"""

from __future__ import annotations

from dataclasses import dataclass, field
import functools
import hashlib
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from greengage_tpu import expr as E
from greengage_tpu import types as T
from greengage_tpu.config import Settings
from greengage_tpu.ops import agg as agg_ops
from greengage_tpu.ops import hashing
from greengage_tpu.ops import join as join_ops
from greengage_tpu.ops import sort as sort_ops
from greengage_tpu.ops.batch import Batch
from greengage_tpu.ops.expr_eval import Evaluator
from greengage_tpu.parallel import SEG_AXIS
from greengage_tpu.parallel import motion as motion_ops
from greengage_tpu.planner.locus import LocusKind
from greengage_tpu.planner.logical import (
    Aggregate, ConstRel, Filter, Join, Limit, Motion, MotionKind, PartialState, Plan,
    Project, Scan, Sort, Union, Window,
)
from greengage_tpu.runtime import devprofile

VALID_PREFIX = "@v:"
# a compacting join's probe rows carry their build row through the
# compaction under this name, which no column id takes
BUILD_ROW = "@build_row"


def _shard_map(fn, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# The plan's names in the device trace, spelled here and nowhere else
# (runtime/devprofile.py and the benchmark's trace_by_node metrics read
# them). Every plan node's function runs under jax.named_scope("<kind>#<n>")
# (Compiler._compile_node: <kind> of NODE_KINDS, <n> the node's preorder
# ordinal in the compiled plan), so each instruction of the executable
# carries the path of the nodes it was emitted for, innermost last. A part
# is a bare scope inside a node's: a step of the node whose label is above
# it in the path (ops/ knows no plan).
NODE_KINDS = ("constrel", "scan", "filter", "project", "join", "semi",
              "agg-sort", "agg-dense", "partialstate", "motion", "window",
              "union", "sort", "limit")
PART_NAMES = ("join-expand", "compact")
JOIN_EXPAND, COMPACT = PART_NAMES

# program metrics that count rows: summed over the segments (in a gang on
# the device, elsewhere by the executor); every other metric sizes a
# per-segment capacity and reports the fullest segment's
SUMMED_METRICS = ("nrows_", "join_null_extended_", "semi_")
# what every semi or anti join reports, one metric each (`<counter>_<n>`),
# counted under the counter's name by the executor: the live build rows its
# table took, those of them whose key an earlier build row holds, the live
# probe rows, and the probe rows it kept
SEMI_COUNTERS = ("semi_build_rows", "semi_build_dup_rows", "semi_probe_rows",
                 "semi_kept_rows")


def _pow2(n: float) -> int:
    m = 1
    while m < n:
        m <<= 1
    return m


def _lag_lead_lookup(fname, param, rn0, n_total, lookup, live):
    """lag/lead via a global position lookup -> (values, valid); the
    SQL-standard default argument replaces out-of-partition offsets.
    Shared by the ordered-global and range window kernels."""
    k, default = param if isinstance(param, tuple) else (param, None)
    p = rn0 - k if fname == "lag" else rn0 + k
    ok = (p >= 0) & (p < n_total)
    val, vv = lookup(p)
    if default is not None:
        val = jnp.where(ok, val, jnp.asarray(default, val.dtype))
        return val, (ok & vv | ~ok) & live
    return val, ok & vv & live


def _static_order_packable(keys, bounds) -> bool:
    """Compile-time mirror of ops/sort.order_pack_bits: the shared bounds
    budget (ops/sort.order_bounds_bits), plus no key may be TEXT (collation
    ranks via rank_lut are unpackable) or FLOAT64 (bounds come from integer
    ANALYZE stats only)."""
    from greengage_tpu.ops import sort as sort_ops

    if any(e.type.kind in (T.Kind.TEXT, T.Kind.FLOAT64)
           for e, _, _ in keys):
        return False
    return sort_ops.order_bounds_bits(bounds, len(keys)) is not None


@dataclass
class CompileResult:
    device_fn: object                  # jitted shard_map program
    input_spec: list                   # [(table, [storage cols], cap)]
    out_cols: list                     # ColInfo list of gather output
    flag_names: list[str]
    gather_child_locus: object
    merge_keys: list | None
    host_limit: tuple | None           # (limit, offset)
    capacity: int                      # below-gather output capacity
    metric_names: list[str] = field(default_factory=list)
    # overflow flag -> (plan node id, metric name): lets the executor size
    # the retry capacity from the exact cardinality the device reported
    flag_caps: dict = field(default_factory=dict)
    # agg_groups metric -> (the out_cap its sort-based aggregate was given,
    # the slots it sorts: its input's capacity, after any compaction)
    agg_caps: dict = field(default_factory=dict)
    # those of them whose group starts the one-pass form finds
    # (ops/agg.group_starts_direct)
    agg_direct: frozenset = frozenset()
    # join_expand_total metric -> (the out_cap its multi join's expansion
    # was given, the join_null_extended metric of a LEFT one | None)
    expand_caps: dict = field(default_factory=dict)
    # the slots each inner or left join gathers its build columns into: the
    # expansion's out_cap of a multi join, else the join's output capacity
    join_gather_slots: tuple = ()
    est_bytes: int = 0                 # rough per-segment device allocation
    node_rows: dict = field(default_factory=dict)  # metric -> plan node id
    # plan-node label ("<kind>#<n>": the scope every operation of the node's
    # function carries) -> id(plan node), the identity node_rows uses
    node_labels: dict = field(default_factory=dict)
    flag_packs: dict = field(default_factory=dict)  # pack flag -> plan nid
    # hoisted-literal parameter slots, in slot order: the executor appends
    # one replicated (1,)-array per slot after the staged table inputs
    param_dtypes: tuple = ()
    # per-node slice of est_bytes (id(plan node) -> bytes, the same
    # identity node_rows uses) for the EXPLAIN ANALYZE per-node Memory
    # annotation
    node_est_bytes: dict = field(default_factory=dict)
    # measured memory accounting (runtime/memaccount.py), filled by the
    # executor at FIRST dispatch and reused on every warm program-cache
    # hit: the AOT-compiled executable (dispatch goes through it so the
    # program compiles exactly once), its memory_analysis dict, and a
    # don't-retry latch for backends where lower/compile/analyze fails.
    # mem_lock serializes the first analysis — two server threads cold-
    # dispatching the same cached program must not both pay the compile
    # (or double-count mem_analysis_runs)
    aot_fn: object = None
    mem_analysis: dict | None = None
    mem_failed: bool = False
    mem_lock: object = field(default_factory=threading.Lock)
    # vectorized serving (exec/batchserve.py): >0 means the program was
    # compiled with a leading member axis — parameters arrive stacked
    # (width, 1) per slot and every output/flag/metric carries a leading
    # (width,) axis the executor demuxes per member. 0 = classic program.
    batch_width: int = 0
    # feedback-store key this program reports measured bytes under
    # (batched programs qualify the statement key with the width bucket,
    # since est_bytes/measured bytes are width-scaled); set by the
    # executor at prepare time, read at dispatch
    fb_key: str | None = None
    # what a `dispatch` span names as its `program`; runtime/devprofile's
    # registry finds this object again by it while something keeps it alive
    program_id: int = field(init=False, default=0)
    _node_map: dict | None = field(init=False, default=None, repr=False)

    def __post_init__(self):
        self.program_id = devprofile.register(self)

    def node_map(self) -> dict | None:
        """{instruction name: scope path} of the executable that runs
        (`aot_fn.as_text()`, each instruction's op_name), parsed at the
        first call and kept; None where there is no AOT executable
        (multihost, `mem_failed`) or its text cannot be had. An executable
        found in the machine's compile cache carries the scopes of the
        process that compiled it: a label may lack its `#<n>`."""
        if self._node_map is None and self.aot_fn is not None:
            with self.mem_lock:
                if self._node_map is None:
                    try:
                        self._node_map = devprofile.parse_node_map(
                            self.aot_fn.as_text())
                    except Exception:
                        return None
        return self._node_map


class Compiler:
    def __init__(self, catalog, store, mesh, nseg: int, consts: dict,
                 settings: Settings, tier: int = 0,
                 cap_overrides: dict | None = None, instrument: bool = False,
                 multihost: bool = False, scan_cap_override: dict | None = None,
                 aux_tables: dict | None = None,
                 pack_disabled: set | None = None, no_direct: bool = False,
                 batch_width: int = 0):
        self.catalog = catalog
        self.store = store
        self.mesh = mesh
        self.nseg = nseg
        # own copy: the session caches the binder's consts dict across
        # executions, and compile stashes per-trace state (runtime param
        # tracers) into its view
        self.consts = dict(consts)
        # hoisted-literal vector (sql/paramize.py): values become traced
        # scalar inputs of the program, so the executable is value-generic
        self.params = self.consts.pop("@params@", None)
        self._consts_digest = self.consts.pop("@consts_digest@", None)
        self.s = settings
        self.tier = tier
        self.cap_overrides = cap_overrides or {}   # plan node id -> capacity
        self.flags: list[str] = []
        self.metrics: list[str] = []
        self.flag_caps: dict = {}
        self.agg_caps: dict = {}           # agg_groups metric -> (out_cap, slots)
        self.agg_direct: set = set()       # ... found by the one-pass form
        self.expand_caps: dict = {}        # join_expand_total metric -> (out_cap, null metric)
        self.join_gather_slots: list[int] = []
        # key packing from ANALYZE bounds: a bounds violation (stale stats)
        # re-runs the SAME tier with that node's packing disabled
        self.pack_disabled = pack_disabled or set()
        self.flag_packs: dict = {}         # pack flag id -> plan node id
        # spill passes force the general hash join: a direct-addressed
        # build allocates its FULL key domain regardless of how small the
        # chunked build scan is, defeating the pass-size search
        self.no_direct = no_direct
        self._reset_scan_state()
        self.instrument = instrument      # EXPLAIN ANALYZE per-node rows
        self.node_rows: dict[str, int] = {}   # metric name -> plan node id
        self.node_labels: dict[str, int] = {}   # scope label -> plan node id
        # multi-host: outputs/flags/metrics are device-reduced + replicated
        # so EVERY process fetches full results and takes identical
        # retry decisions (parallel/multihost.py lockstep invariants)
        self.multihost = multihost
        # spill support (exec/spill.py): chunked scan capacities and
        # host-staged ephemeral inputs ("@spill:" tables)
        self.scan_cap_override = scan_cap_override or {}
        self.aux_tables = aux_tables or {}
        # vectorized serving (exec/batchserve.py): wrap the per-member
        # program in a vmap over the stacked parameter inputs. Staged
        # table inputs are closed over (broadcast — every member scans the
        # same data); only parameters carry the member axis. Under
        # multihost the coordinator broadcasts the whole batch window
        # (op sql_batch) so every gang member compiles this same
        # width-bucketed program and its collectives rendezvous exactly
        # like a classic statement's.
        self.batch_width = int(batch_width)

    def _reset_scan_state(self) -> None:
        """Fresh per-walk scan collection: compile() re-resets so ONE
        Compiler can run shape_signature() and then compile() (the
        executor's miss path) without double-counting scan_count, which
        would silently disable single-scan zone pruning."""
        self.scan_caps: dict[str, int] = {}
        self.scan_cols: dict[str, set] = {}
        self.scan_direct: dict[str, int | None] = {}  # table -> pinned seg
        self.scan_count: dict[str, int] = {}
        self.scan_prune: dict[str, tuple] = {}        # table -> pushed preds
        self.scan_parts: dict[str, tuple | None] = {}  # table -> child tables
        self.scan_dyn: dict[str, tuple | None] = {}   # table -> dyn prune src

    def _merge_unpinned_scan_caps(self) -> None:
        """No (consistent) direct pin: the staged capacity must cover EVERY
        segment, not just the pinned ones two conflicting point-scans named
        (their caps were merged into scan_caps). Runs in BOTH compile() and
        shape_signature() so the signature digests the same post-merge caps
        the trace allocates — otherwise DML growing a NON-pinned segment
        past its bucket could leave the signature equal and reuse a
        too-small executable."""
        for t in sorted(self.scan_caps):
            if self.scan_direct.get(t) is None and t not in self.aux_tables:
                counts = self._seg_counts(t, self.scan_parts.get(t))
                self.scan_caps[t] = max(
                    self.scan_caps[t],
                    self._bucket_cap(t, max(counts, default=0)))

    # ------------------------------------------------------------------
    def compile(self, plan: Motion) -> CompileResult:
        assert isinstance(plan, Motion) and plan.kind is MotionKind.GATHER
        self._reset_scan_state()
        # Stable plan-node identity: preorder ordinals over the plan tree.
        # cap_overrides / pack_disabled / flag_caps / flag_packs cross
        # compile invocations through the executor's retry loop and plan
        # cache, where the SAME statement may be re-planned into fresh node
        # objects — id() would dangle (advisor r3), ordinals are stable
        # because re-planning the same statement is deterministic.
        self._nids: dict[int, int] = {}
        stack = [plan]
        while stack:
            p = stack.pop()
            self._nids[id(p)] = len(self._nids)
            stack.extend(reversed(p.children))
        below = plan.child
        self._dict_refs: dict[str, tuple] = {}
        _collect_dict_refs(plan, self._dict_refs)
        # host-side limit/merge bookkeeping: ONLY the Limit directly below
        # the gather gets its OFFSET trimmed on the host; buried Limits must
        # drop their offset prefix on device (_c_limit)
        host_limit = None
        self._host_limit_node = None
        node = below
        if isinstance(node, Limit):
            host_limit = (node.limit, node.offset)
            self._host_limit_node = id(node)

        self._collect_scans(below)
        self._merge_unpinned_scan_caps()
        input_spec = []
        for t in sorted(self.scan_caps):
            cols = []
            for c in sorted(self.scan_cols[t]):
                cols.append(c)
                if t in self.aux_tables:
                    if self.aux_tables[t][1].get(c) is not None:
                        cols.append(VALID_PREFIX + c)
                elif self.store.has_nulls(t, c):
                    cols.append(VALID_PREFIX + c)
            # zone-map pruning applies only when this table is scanned once
            # (a second scan would need the pruned-away rows) and carries
            # no raw-text surrogates (their row numbering must stay whole)
            prune = self.scan_prune.get(t) or None
            if prune and (self.scan_count.get(t, 0) != 1 or any(
                    c.startswith(("@hp:", "@rc:", "@rp:", "@rl:", "@rw:"))
                    for c in cols)):
                prune = None
            if prune:
                schema_t = self.catalog.get(t)
                if any(col.type.kind == T.Kind.TEXT and col.encoding == "raw"
                       for col in schema_t.columns if col.name in self.scan_cols[t]):
                    prune = None
            dyn = self.scan_dyn.get(t)
            if not isinstance(dyn, tuple):
                dyn = None
            input_spec.append((t, cols, self.scan_caps[t],
                               self.scan_direct.get(t), prune,
                               self.scan_parts.get(t), dyn))

        compiled = self._compile_node(below)   # closure: ctx -> Batch
        out_cols = below.out_cols()

        # Device-side result compaction before the Gather (Gather Motion,
        # nodeMotion.c:171): shipping nseg x capacity padded rows to the
        # host for a selective result is pathological. When estimated live
        # rows sit far below capacity, stable-sort live-first (2 operands)
        # and ship a small static slice; the exact live count feeds the
        # overflow retry. Sorts/Limits already compact; Aggregate outputs
        # are dense domains or group tables numbered live-first.
        cap_below = self._capacity_of(below)
        compact_k = self._gather_compact_k(plan, below)
        fid_cmp = mid_cmp = None
        if compact_k is not None:
            fid_cmp = f"gather_compact_overflow_{len(self.flags)}"
            self.flags.append(fid_cmp)
            mid_cmp = f"gather_compact_total_{len(self.metrics)}"
            self.metrics.append(mid_cmp)
            self.flag_caps[fid_cmp] = (self._nid(plan), mid_cmp)

        flag_names = list(self.flags)
        nseg = self.nseg

        mh = self.multihost
        metric_names = list(self.metrics)
        # hoisted-literal parameters (sql/paramize.py): one replicated
        # (1,)-scalar input per slot, read by Evaluator._eval_param — the
        # executable stays value-generic, values bind per dispatch
        param_dtypes = ()
        if self.params is not None and self.params.values:
            param_dtypes = tuple(t.np_dtype for t in self.params.types)
        nparams = len(param_dtypes)

        def seg_fn(*flat):
            from jax import lax

            ctx = {"tables": {}, "flags": []}
            i = 0
            for tname, cols, cap, _direct, _prune, _parts, _dyn in input_spec:
                entry = {}
                for c in cols:
                    entry[c] = flat[i]
                    i += 1
                entry["@present"] = flat[i]
                i += 1
                ctx["tables"][tname] = entry
            if nparams:
                # visible to every Evaluator(b, self.consts) in the
                # compiled closures; self.consts is this Compiler's copy,
                # so the tracers never leak into the session's cached pool
                self.consts["@params@rt"] = {
                    k: flat[i + k] for k in range(nparams)}
                i += nparams
            ctx["metrics"] = []
            batch = compiled(ctx)
            sel = batch.selection()
            if compact_k is not None:
                dead = (~sel).astype(jnp.uint8)
                rid = jnp.arange(sel.shape[0], dtype=jnp.int32)
                _, perm = lax.sort((dead, rid), num_keys=2)
                perm = perm[:compact_k]
                total = jnp.sum(sel.astype(jnp.int32))
                ctx["flags"].append((fid_cmp, total > compact_k))
                ctx["metrics"].append((mid_cmp, total))
                batch = Batch(
                    {c.id: batch.cols[c.id][perm] for c in out_cols},
                    {c.id: batch.valids[c.id][perm] for c in out_cols
                     if batch.valids.get(c.id) is not None},
                    jnp.arange(compact_k, dtype=jnp.int32) < total)
                sel = batch.selection()
            outs = []
            for c in out_cols:
                outs.append(batch.cols[c.id])
                v = batch.valids.get(c.id)
                outs.append(jnp.ones_like(sel) if v is None else v)
            outs.append(sel)
            if mh:
                # gather every segment's shard on device so all processes
                # hold the full result (the Gather Motion as a collective)
                outs = [lax.all_gather(o, SEG_AXIS) for o in outs]
            # emit in REGISTRATION order (flag_names/metric_names) — the
            # executor zips values against those name lists, and operators
            # may append to ctx in a different order than they registered
            fdict = dict(ctx["flags"])
            assert len(fdict) == len(flag_names), (
                sorted(fdict), sorted(flag_names))
            for name in flag_names:
                f = fdict[name].astype(jnp.int32)
                if mh:
                    f = lax.pmax(f, SEG_AXIS)
                outs.append(jnp.broadcast_to(f, (1,)))
            mdict = dict(ctx["metrics"])
            for name in metric_names:
                m = mdict[name].astype(jnp.int64)
                if mh:
                    m = (lax.psum(m, SEG_AXIS) if name.startswith(SUMMED_METRICS)
                         else lax.pmax(m, SEG_AXIS))
                outs.append(jnp.broadcast_to(m, (1,)))
            return tuple(outs)

        # vectorized serving (docs/PERF.md "Vectorized serving"): the same
        # per-member program body, vmapped over the stacked parameter
        # inputs — each slot arrives (width, 1) and every member instance
        # sees the classic (1,) contract. Table inputs are closed over
        # (broadcast: every member scans the same staged data); outputs,
        # flags, and metrics gain a leading (width,) member axis the
        # executor demuxes. Kept as a SEPARATE closure so the classic
        # program's jaxpr — and its persistent-XLA-cache entries — stay
        # byte-identical when batching is off.
        W = self.batch_width

        def seg_fn_batched(*flat):
            from jax import lax

            tables = {}
            i = 0
            for tname, cols, cap, _direct, _prune, _parts, _dyn in input_spec:
                entry = {}
                for c in cols:
                    entry[c] = flat[i]
                    i += 1
                entry["@present"] = flat[i]
                i += 1
                tables[tname] = entry
            pstack = flat[i:i + nparams]    # each (W, 1)

            def one_member(pflat):
                ctx = {"tables": dict(tables), "flags": [], "metrics": []}
                self.consts["@params@rt"] = {
                    k: pflat[k] for k in range(nparams)}
                batch = compiled(ctx)
                sel = batch.selection()
                if compact_k is not None:
                    dead = (~sel).astype(jnp.uint8)
                    rid = jnp.arange(sel.shape[0], dtype=jnp.int32)
                    _, perm = lax.sort((dead, rid), num_keys=2)
                    perm = perm[:compact_k]
                    total = jnp.sum(sel.astype(jnp.int32))
                    ctx["flags"].append((fid_cmp, total > compact_k))
                    ctx["metrics"].append((mid_cmp, total))
                    batch = Batch(
                        {c.id: batch.cols[c.id][perm] for c in out_cols},
                        {c.id: batch.valids[c.id][perm] for c in out_cols
                         if batch.valids.get(c.id) is not None},
                        jnp.arange(compact_k, dtype=jnp.int32) < total)
                    sel = batch.selection()
                outs = []
                for c in out_cols:
                    outs.append(batch.cols[c.id])
                    v = batch.valids.get(c.id)
                    outs.append(jnp.ones_like(sel) if v is None else v)
                outs.append(sel)
                fdict = dict(ctx["flags"])
                assert len(fdict) == len(flag_names), (
                    sorted(fdict), sorted(flag_names))
                for name in flag_names:
                    outs.append(jnp.broadcast_to(
                        fdict[name].astype(jnp.int32), (1,)))
                mdict = dict(ctx["metrics"])
                for name in metric_names:
                    outs.append(jnp.broadcast_to(
                        mdict[name].astype(jnp.int64), (1,)))
                return tuple(outs)

            return jax.vmap(one_member)(pstack)

        ncols_out = 2 * len(out_cols) + 1
        nouts = ncols_out + len(flag_names) + len(metric_names)
        if W:
            assert nparams, "a batched program needs parameter inputs"
            # outputs carry a leading member axis; segments concatenate
            # along axis 1 -> global (W, nseg * cap) per output
            out_specs = tuple([P(None, SEG_AXIS)] * nouts)
        elif mh:
            out_specs = tuple([P()] * nouts)
        else:
            out_specs = tuple([P(SEG_AXIS)] * nouts)
        # the Gather has no function of its own: what the program does above
        # the plan below it (the compaction, the outputs, the flags) runs
        # under the Gather's label, the nodes below under theirs inside it
        body, gather = seg_fn_batched if W else seg_fn, self._label(plan)

        @functools.wraps(body)   # the jitted name is part of the cache key
        def under_gather(*flat):
            with jax.named_scope(gather):
                return body(*flat)

        fn = jax.jit(
            _shard_map(
                under_gather,
                mesh=self.mesh,
                in_specs=tuple(P(SEG_AXIS) for _ in range(
                    sum(len(c) + 1 for _, c, *_ in input_spec)))
                + tuple(P() for _ in range(nparams)),
                out_specs=out_specs,
            )
        )
        return CompileResult(
            device_fn=fn,
            input_spec=input_spec,
            out_cols=out_cols,
            flag_names=flag_names,
            gather_child_locus=below.locus,
            merge_keys=plan.merge_keys,
            host_limit=host_limit,
            capacity=compact_k if compact_k is not None
            else self._capacity_of(below),
            metric_names=metric_names,
            flag_caps=dict(self.flag_caps),
            agg_caps=dict(self.agg_caps),
            agg_direct=frozenset(self.agg_direct),
            expand_caps=dict(self.expand_caps),
            join_gather_slots=tuple(self.join_gather_slots),
            # a batched program holds ~one member's intermediates PER
            # member (vmap), while the staged scan args are shared; charge
            # the conservative width multiple — admission over-refusing a
            # wide batch only narrows it to serial execution, never fails
            est_bytes=self._estimate_bytes(below) * max(W, 1),
            node_est_bytes=dict(self.node_est_bytes),
            node_rows=dict(self.node_rows),
            node_labels=dict(self.node_labels),
            flag_packs=dict(self.flag_packs),
            param_dtypes=param_dtypes,
            batch_width=W,
        )

    def _nid(self, plan) -> int:
        """Stable preorder ordinal of a plan node (see compile())."""
        return self._nids[id(plan)]

    def _gather_compact_k(self, plan, below) -> int | None:
        """Device-side result-compaction slot count before the Gather, or
        None when the result ships uncompacted (shared by compile() and
        shape_signature — the decision is part of the program's shape)."""
        cap_below = self._capacity_of(below)
        if isinstance(below, (Sort, Limit, Aggregate, PartialState)) \
                or cap_below < (1 << 14):
            return None
        est = max(getattr(below, "est_rows", 0.0) or 0.0, 1.0)
        if below.locus is not None and below.locus.is_partitioned \
                and self.nseg > 1:
            est /= self.nseg
        k = _pow2(int(est * 1.5) + 64) * (4 ** self.tier)
        if self._nid(plan) in self.cap_overrides:
            k = _pow2(int(self.cap_overrides[self._nid(plan)]))
        if k * 2 <= cap_below:
            return min(k, cap_below)
        return None

    # ------------------------------------------------------------------
    # shape signature: the executable-reuse key half (docs/PERF.md)
    # ------------------------------------------------------------------
    _SIG_SKIP_FIELDS = frozenset((
        # tree edges (walked explicitly) and estimate-only fields — the
        # estimates' influence on the program is via the BUCKETED
        # capacities, which the signature captures separately
        "child", "left", "right", "inputs", "est_rows", "expand_est",
        "locus", "parts_total", "index_hits",
    ))

    def shape_signature(self, plan: Motion, snapshot=None) -> str:
        """Digest of EVERYTHING the traced program reads at compile time:
        plan structure + expression trees (pinned literal values and Param
        slots included), pow2-bucketed per-node capacities, referenced
        dictionary contents (fingerprints), the binder's consts pool
        digest, parameter dtypes, and the codegen-relevant settings.

        Equal signature => compiling this plan would produce an identical
        XLA program, so the executor's program cache can reuse the
        compiled executable ACROSS manifest versions: a DML that stays
        inside every capacity bucket and grows no dictionary re-dispatches
        the hot executable instead of recompiling."""

        self._snap = snapshot
        self._nids = {}
        stack = [plan]
        while stack:
            p = stack.pop()
            self._nids[id(p)] = len(self._nids)
            stack.extend(reversed(p.children))
        below = plan.child
        self._dict_refs = {}
        _collect_dict_refs(plan, self._dict_refs)
        # node-identity marker compared against id(p) during this same
        # walk — never digested into the payload
        self._host_limit_node = (
            id(below) if isinstance(below, Limit) else None)  # gg:ok(tracer)
        self._collect_scans(below)
        self._merge_unpinned_scan_caps()
        nodes = []
        dict_refs: dict = dict(self._dict_refs)
        stack = [plan]
        while stack:
            p = stack.pop()
            stack.extend(reversed(p.children))
            fields = []
            for name, v in vars(p).items():
                if name in self._SIG_SKIP_FIELDS:
                    continue
                fields.append((name, repr(v)))
                _collect_value_dict_refs(v, dict_refs)
            try:
                cap = self._capacity_of(p)
            except NotImplementedError:
                cap = -1
            extra = []
            if isinstance(p, Join) and getattr(p, "multi", False) \
                    and p.kind in ("semi", "anti"):
                extra.append(self._join_multi_expand_cap(p))
            if isinstance(p, (Join, Aggregate)):
                # the compaction of a build side or a sort-aggregate's
                # input follows estimates the fields above leave out
                extra.append(self._compact_k(p.children[-1]))
            nodes.append((type(p).__name__,
                          p.locus.kind.name if p.locus is not None else None,
                          cap, tuple(extra), tuple(fields)))
        dicts = []
        for ref in sorted(set(dict_refs.values())):
            try:
                dicts.append((ref, self.store.dictionary(*ref).fingerprint()))
            except Exception:
                # unresolved ref (e.g. evicted transient raw dict): the
                # caller treats a failed signature as uncacheable
                raise LookupError(f"dictionary {ref} unavailable")
        s = self.s
        settings_sig = (self.nseg, self.multihost, self.tier,
                        tuple(sorted(self.pack_disabled)),
                        self.no_direct) + self.codegen_settings_sig(s)
        pdtypes = ()
        if self.params is not None:
            pdtypes = tuple(str(t.np_dtype) for t in self.params.types)
        gather_k = self._gather_compact_k(plan, below)
        payload = repr((tuple(nodes), tuple(dicts), self._consts_digest,
                        pdtypes, gather_k, settings_sig))
        return hashlib.sha1(payload.encode()).hexdigest()

    @staticmethod
    def codegen_settings_sig(s) -> tuple:
        """Every Settings field shape_signature digests. The executor keys
        its per-dispatch signature memo on this same tuple, so a SET that
        changes codegen invalidates memoized signatures, never a stale
        executable lookup."""
        return (s.dense_group_limit, s.motion_capacity_slack,
                s.motion_pipeline_buckets,
                s.hash_num_probes, s.hash_table_min, s.hash_table_max)

    def _estimate_bytes(self, plan: Plan) -> int:
        """Rough per-segment device allocation for the whole program
        (vmem_tracker admission analog): every node's batch capacity times
        its column widths, summed over the tree. Records the per-node
        slices in ``node_est_bytes`` (same id(node) identity as node_rows)
        so EXPLAIN ANALYZE can print a per-node Memory annotation."""
        total = 0
        self.node_est_bytes: dict[int, int] = {}
        stack = [plan]
        while stack:
            p = stack.pop()
            try:
                cap = self._capacity_of(p)
            except NotImplementedError:
                cap = 0
            width = sum(max(c.type.np_dtype.itemsize, 1) + 1 for c in p.out_cols())
            node_bytes = cap * width
            if isinstance(p, Motion) and self._motion_is_identity(p):
                node_bytes = 0   # its child's batch, not a copy of it
            if isinstance(p, Window) \
                    and getattr(p, "global_mode", False) in ("ordered",
                                                             "range"):
                # all-gathered sorted key runs [nseg, cap] (8B keys) plus
                # one gathered (value, valid) run per positional function
                # argument — the real footprint of the gather-free path
                extra = cap * self.nseg * 9
                for _ci, fname, arg, _o, _pp in p.wfuncs:
                    if fname in ("lag", "lead", "first_value",
                                 "last_value") and arg is not None:
                        extra += cap * self.nseg * (
                            max(arg.type.np_dtype.itemsize, 1) + 1)
                node_bytes += extra
            if isinstance(p, Join):
                if getattr(p, "direct_domain", None) is not None \
                        and self.tier == 0 and not self.no_direct:
                    # dense build table: slot_row/counts int32 + int64 temps
                    node_bytes += int(p.direct_domain) * 16
                else:
                    try:
                        node_bytes += self._join_table_size(
                            self._capacity_of(p.right)) * 16
                    except NotImplementedError:
                        pass
            self.node_est_bytes[id(p)] = node_bytes
            total += node_bytes
            stack.extend(p.children)
        return total

    # ------------------------------------------------------------------
    # capacities
    # ------------------------------------------------------------------
    def _seg_counts(self, table: str, parts: tuple | None = None) -> list[int]:
        """Per-segment row counts, clamped by any spill chunk override.
        A partitioned scan sums its (pruned) child tables — pruning
        therefore shrinks the staged capacity, not just the IO."""
        snap = getattr(self, "_snap", None)
        if parts is not None:
            # one manifest snapshot for all children (it is a full-file
            # JSON parse; per-child reads would be O(parts) disk parses)
            snap = snap or self.store.manifest.snapshot()
            per = [self.store.segment_rowcounts(p, snap) for p in parts]
            counts = [sum(c[s] for c in per)
                      for s in range(self.nseg)] if per else [0] * self.nseg
        else:
            counts = self.store.segment_rowcounts(table, snap)
        cap = self.scan_cap_override.get(table)
        if cap is not None:
            counts = [min(c, cap) for c in counts]
        return counts

    def _bucket_cap(self, table: str, cap: int) -> int:
        """Round a scan capacity up to its pow2 bucket: a DML that stays
        within the bucket compiles to the SAME program shape, so the
        executor's executable cache survives manifest-version bumps
        (docs/PERF.md "plan cache"). Spill chunk overrides are exact pass
        boundaries — growing them would double-read rows across passes."""
        if table in self.scan_cap_override:
            return max(cap, 1)
        return _pow2(max(cap, 1))

    def _collect_scans(self, plan: Plan):
        if isinstance(plan, Scan):
            if plan.table in self.aux_tables:
                cols0 = self.aux_tables[plan.table][0]
                n = len(next(iter(cols0.values()))) if cols0 else 0
                cap = max(-(-max(n, 1) // self.nseg), 1)
                self.scan_caps[plan.table] = max(
                    self.scan_caps.get(plan.table, 0), cap)
                self.scan_cols.setdefault(plan.table, set()).update(
                    c.name for c in plan.cols)
                self.scan_direct[plan.table] = None
                self.scan_count[plan.table] = self.scan_count.get(plan.table, 0) + 1
                self.scan_prune[plan.table] = ()
                for c in plan.children:
                    self._collect_scans(c)
                return
            counts = self._seg_counts(plan.table, plan.parts)
            ds = plan.direct_seg
            if ds is not None and 0 <= ds < len(counts):
                cap = max(counts[ds], 1)
            else:
                cap = max(max(counts, default=0), 1)
            cap = self._bucket_cap(plan.table, cap)
            self.scan_caps[plan.table] = max(self.scan_caps.get(plan.table, 0), cap)
            self.scan_cols.setdefault(plan.table, set()).update(c.name for c in plan.cols)
            # direct dispatch only holds if EVERY scan of the table agrees
            prev = self.scan_direct.get(plan.table, "unset")
            self.scan_direct[plan.table] = ds if prev in ("unset", ds) else None
            self.scan_count[plan.table] = self.scan_count.get(plan.table, 0) + 1
            self.scan_prune[plan.table] = tuple(plan.prune_preds or ())
            # two scans of one parent stage the UNION of their live parts
            if plan.parts is not None:
                prev_parts = self.scan_parts.get(plan.table)
                merged = (tuple(dict.fromkeys((prev_parts or ()) + plan.parts))
                          if prev_parts is not None else plan.parts)
                self.scan_parts[plan.table] = merged
            else:
                self.scan_parts.setdefault(plan.table, None)
            # join-driven runtime pruning annotation; two scans with
            # different sources cannot share one prune — disable
            dyn = getattr(plan, "dyn_prune", None)
            prev_dyn = self.scan_dyn.get(plan.table, "unset")
            self.scan_dyn[plan.table] = (dyn if prev_dyn in ("unset", dyn)
                                         else None)
        for c in plan.children:
            self._collect_scans(c)

    def _capacity_of(self, plan: Plan) -> int:
        """Static per-segment row capacity of a node's output batch."""
        if isinstance(plan, ConstRel):
            return 1
        if isinstance(plan, Scan):
            if plan.table in self.scan_caps:
                return self.scan_caps[plan.table]
            return self._bucket_cap(
                plan.table,
                max(self._seg_counts(plan.table, plan.parts), default=0))
        if isinstance(plan, (Filter, Project, Sort, Window)):
            return self._capacity_of(plan.child)
        if isinstance(plan, Limit):
            cap = self._capacity_of(plan.child)
            if plan.limit is not None:
                return min(cap, plan.limit + plan.offset)
            return cap
        if isinstance(plan, Join):
            probe_cap = self._capacity_of(plan.left)
            if plan.kind == "cross":
                return probe_cap * max(self._capacity_of(plan.right), 1)
            if getattr(plan, "multi", False) and plan.kind in ("inner", "left"):
                if self._nid(plan) in self.cap_overrides:
                    # exact cardinality reported by the overflowed run
                    # (pow2 bucket: shape-stable across small DML)
                    return _pow2(max(int(self.cap_overrides[self._nid(plan)]),
                                     64))
                # CSR expansion output capacity from the (stats-driven)
                # cardinality estimate; est_rows is CLUSTER-GLOBAL, the
                # batch is per segment — divide by width for partitioned
                # loci (skew is caught by the exact-count overflow retry)
                est = max(plan.est_rows, 64.0) * 1.5
                if plan.locus is not None and plan.locus.is_partitioned \
                        and self.nseg > 1:
                    est /= self.nseg
                base = max(int(est) + 64, probe_cap // 4)
                return _pow2(base) * (4 ** self.tier)
            return self._join_compact_k(plan) or probe_cap
        if isinstance(plan, Aggregate):
            if not plan.group_keys:
                return 1
            dense = self._dense_domains(plan)
            if dense is not None:
                d = 1
                for dom in dense:
                    d *= dom
                return d
            # sort-based path: output capacity = estimated group count with
            # slack; can never exceed the child batch (groups <= rows), and
            # an exact-count retry tightens it after overflow
            child_cap = self._compact_k(plan.child) \
                or self._capacity_of(plan.child)
            if self._nid(plan) in self.cap_overrides:
                return min(_pow2(max(int(self.cap_overrides[self._nid(plan)]),
                                     64)),
                           child_cap)
            # at least AGG_MIN_SLOTS: a small table costs nothing, and an
            # estimate that moves below it keeps its program
            est = int(max(plan.est_rows, 16.0) * 1.3) + 64
            return min(max(_pow2(est), self.AGG_MIN_SLOTS) * (4 ** self.tier),
                       child_cap)
        if isinstance(plan, PartialState):
            return self._capacity_of(plan.child)
        if isinstance(plan, Union):
            return sum(self._capacity_of(c) for c in plan.inputs)
        if isinstance(plan, Motion):
            child_cap = self._capacity_of(plan.child)
            if self._motion_is_identity(plan):
                return child_cap
            if plan.kind is MotionKind.BROADCAST:
                return child_cap * self.nseg
            if plan.kind is MotionKind.REDISTRIBUTE:
                return self.nseg * self._motion_bucket(child_cap)
            return child_cap
        raise NotImplementedError(type(plan).__name__)

    def _motion_is_identity(self, plan: Motion) -> bool:
        """On one segment a Redistribute or a Broadcast has nowhere to send
        a row: every row is already where it would go, so the Motion is its
        child's batch (no hash, no bucketize scatter, no exchange with
        itself). The plan keeps the node; only the program loses it."""
        return self.nseg == 1 and plan.kind in (MotionKind.REDISTRIBUTE,
                                                MotionKind.BROADCAST)

    def _motion_bucket(self, child_cap: int) -> int:
        c = int(child_cap * self.s.motion_capacity_slack / self.nseg) + 64
        c = _pow2(c) * (4 ** self.tier)
        return min(c, child_cap)

    # a batch keeps its producer's capacity however few rows survive, and a
    # sort or a scatter build pays for every slot of it. Where the planner
    # expects the live rows to fit 1/COMPACT_RATIO of the slots twice over,
    # the consumer first gathers them into a batch of that size
    # (sort_ops.compact: a cumsum and a binary search, no sort). The size
    # follows the capacity, not the estimate: an estimate that the feedback
    # store corrects a hundredfold must not ask for another program (on the
    # TPU, another compile of many minutes). An inner join applies the same
    # rule to its own output (_join_compact_k): it compacts its matched rows
    # before it gathers the build columns, so the gathers and every node
    # above it pay for 1/COMPACT_RATIO of the probe side's slots.
    COMPACT_RATIO = 32
    AGG_MIN_SLOTS = 4096

    def _compact_k(self, node: Plan, cap: int | None = None) -> int | None:
        """Slots to compact ``node``'s output into before a consumer that
        pays per capacity row, or None where it stays as it is. ``cap`` is
        the batch's capacity where it is not ``node``'s own."""
        if cap is None:
            cap = self._capacity_of(node)
        k = _pow2(cap) // self.COMPACT_RATIO
        need = self.cap_overrides.get(-1 - self._nid(node))
        if need is None:
            need = getattr(node, "est_rows", None)
            if not need:
                return None
            if node.locus is not None and node.locus.is_partitioned \
                    and self.nseg > 1:
                need /= self.nseg
            need = need * 2 + 64
        # (an override is the exact live count of a run that overflowed: if
        # that no longer fits, the batch is not worth compacting)
        return k if need <= k else None

    def _join_compact_k(self, plan: Join) -> int | None:
        """Slots an inner join compacts its matched probe rows into before
        it gathers the build columns, or None: the consumer's rule applied
        to the join's output, sized from the probe side's capacity. A LEFT
        join keeps its unmatched rows, a semi or anti join gathers nothing,
        a multi join's expansion is sized from the estimate already."""
        if plan.kind != "inner" or getattr(plan, "multi", False):
            return None
        return self._compact_k(plan, self._capacity_of(plan.left))

    def _compaction(self, node: Plan, k: int):
        """The compaction of ``node``'s output into ``k`` slots -> a function
        (ctx, cols, valids, live) -> (cols, valids without Nones, sel). More
        live rows than slots raises a flag, and the exact count sizes the
        retry (cap_overrides under -1 - the node's ordinal: the ordinal
        itself may already size the node's own output)."""
        fid = f"compact_overflow_{len(self.flags)}"
        self.flags.append(fid)
        mid = f"compact_live_{len(self.metrics)}"
        self.metrics.append(mid)
        self.flag_caps[fid] = (-1 - self._nid(node), mid)

        def compact(ctx, cols, valids, live):
            with jax.named_scope(COMPACT):
                cols, valids, sel = sort_ops.compact(cols, valids, live, k)
                total = jnp.sum(live.astype(jnp.int32))
                ctx["flags"].append((fid, total > k))
                ctx["metrics"].append((mid, total))
            return cols, {n: v for n, v in valids.items() if v is not None}, sel

        return compact

    def _compacted(self, node: Plan, fn):
        """-> (fn or fn followed by the compaction, its output capacity)."""
        k = self._compact_k(node)
        if k is None:
            return fn, self._capacity_of(node)
        compact = self._compaction(node, k)

        def run(ctx):
            b = fn(ctx)
            return Batch(*compact(ctx, b.cols, b.valids, b.selection()))

        return run, k

    def _dense_domains(self, plan: Aggregate) -> list[int] | None:
        """Per-key dense domains (|dict|+1 / bool 3) when every group key has
        a known finite domain and the product fits the dense limit."""
        if not plan.group_keys:
            return None
        domains = []
        prod = 1
        for ci, e in plan.group_keys:
            if ci.type.kind is T.Kind.TEXT:
                d = getattr(e, "_dict_ref", None) or ci.dict_ref
                if d is None and isinstance(e, E.ColRef):
                    d = self._dict_refs.get(e.name)
                if d is None:
                    return None
                domains.append(len(self.store.dictionary(*d)) + 1)
            elif ci.type.kind is T.Kind.BOOL:
                domains.append(3)
            else:
                return None
            prod *= domains[-1]
            if prod > self.s.dense_group_limit:
                return None
        return domains

    def _join_table_size(self, build_cap: int) -> int:
        # 3x headroom keeps the load factor under ~1/3: expected chain ~1.5
        # rounds, and the dynamic-trip probe loop only pays what it walks
        m = _pow2(build_cap * 3) * (4 ** self.tier)
        return max(self.s.hash_table_min, min(m, self.s.hash_table_max))

    def _join_probes(self) -> int:
        return self.s.hash_num_probes * (2 ** min(self.tier, 2))

    # ------------------------------------------------------------------
    # node compilation (returns closures ctx -> Batch)
    # ------------------------------------------------------------------
    def _scope_name(self, plan: Plan) -> str:
        """The plan node's kind in the device trace, one of NODE_KINDS."""
        if isinstance(plan, Join):
            return "semi" if plan.kind in ("semi", "anti") else "join"
        if isinstance(plan, Aggregate):
            dense = not plan.group_keys or self._dense_domains(plan) is not None
            return "agg-dense" if dense else "agg-sort"
        return type(plan).__name__.lower()

    def _label(self, plan: Plan) -> str:
        """The plan node's name in the device trace, "<kind>#<n>": every
        operation its function emits carries it (jax.named_scope),
        innermost last. Kept with the node's identity for the readers
        that go from an operation back to the plan (node_labels)."""
        label = f"{self._scope_name(plan)}#{self._nid(plan)}"
        self.node_labels[label] = id(plan)
        return label

    def _compile_node(self, plan: Plan):
        inner = getattr(self, "_c_" + type(plan).__name__.lower())(plan)
        scope = self._label(plan)

        def fn(ctx):
            with jax.named_scope(scope):
                return inner(ctx)

        if not self.instrument:
            # always-on row counters on Filter outputs: selectivity is the
            # estimate the planner gets most wrong, and one jnp.sum per
            # Filter is cheap enough to leave on for every normal run so
            # the feedback store sees actuals without EXPLAIN ANALYZE
            if isinstance(plan, Filter):
                mid = f"nrows_{len(self.metrics)}"
                self.metrics.append(mid)
                self.node_rows[mid] = id(plan)

                def counted_f(ctx):
                    b = fn(ctx)
                    ctx["metrics"].append(
                        (mid, jnp.sum(b.selection().astype(jnp.int64))))
                    return b

                return counted_f
            return fn
        # per-node output row counter (the INSTRUMENT_CDB / explain_gp.c
        # per-operator Instrumentation analog): one cheap reduction per node
        mid = f"nrows_{len(self.metrics)}"
        self.metrics.append(mid)
        self.node_rows[mid] = id(plan)

        def counted(ctx):
            b = fn(ctx)
            ctx["metrics"].append(
                (mid, jnp.sum(b.selection().astype(jnp.int64))))
            return b

        return counted

    def _c_constrel(self, plan):
        def run(ctx):
            from jax import lax

            sel = (lax.axis_index(SEG_AXIS) == 0)[None]   # [1], seg0 only
            return Batch({}, {}, sel)

        return run

    def _c_scan(self, plan: Scan):
        table = plan.table
        id_by_store = [(c.id, c.name) for c in plan.cols]

        def run(ctx):
            t = ctx["tables"][table]
            cols = {cid: t[sname] for cid, sname in id_by_store}
            valids = {
                cid: t[VALID_PREFIX + sname]
                for cid, sname in id_by_store
                if VALID_PREFIX + sname in t
            }
            return Batch(cols, valids, t["@present"])

        return run

    def _c_filter(self, plan: Filter):
        child = self._compile_node(plan.child)
        pred = plan.predicate

        def run(ctx):
            b = child(ctx)
            mask = Evaluator(b, self.consts).predicate(pred)
            return b.with_sel(b.selection() & mask)

        return run

    def _c_project(self, plan: Project):
        child = self._compile_node(plan.child)
        exprs = plan.exprs

        def run(ctx):
            b = child(ctx)
            ev = Evaluator(b, self.consts)
            cols, valids = {}, {}
            for ci, e in exprs:
                v, valid = ev.value(e)
                cols[ci.id] = v
                if valid is not None:
                    valids[ci.id] = valid
            return Batch(cols, valids, b.sel)

        return run

    # ---- joins ---------------------------------------------------------
    def _key_specs(self, batch: Batch, exprs):
        ev = Evaluator(batch, self.consts)
        specs = []
        for e in exprs:
            v, valid = ev.value(e)
            lut = None
            if e.type.kind is T.Kind.TEXT:
                d = getattr(e, "_dict_ref", None)
                if d is None and isinstance(e, E.ColRef):
                    d = self._dict_for_col(e.name)
                if d is not None:
                    lut = jnp.asarray(self.store.dictionary(*d).hashes())
            specs.append(agg_ops.KeySpec(v, valid, e.type, hash_lut=lut))
        return specs

    def _dict_for_col(self, col_id: str):
        return self._dict_refs.get(col_id)

    def _c_join_cross(self, plan: Join):
        """Cartesian pairing by repeat/tile index expansion — practical for
        the small (usually broadcast single-row ConstRel) build sides the
        planner produces; capacity = |L| x |B| keeps it honest under the
        vmem admission estimate for anything bigger."""
        left_fn = self._compile_node(plan.left)
        right_fn = self._compile_node(plan.right)
        Lcap = self._capacity_of(plan.left)
        Bcap = max(self._capacity_of(plan.right), 1)

        def run(ctx):
            lb = left_fn(ctx)
            rb = right_fn(ctx)
            li = jnp.repeat(jnp.arange(Lcap), Bcap)
            ri = jnp.tile(jnp.arange(Bcap), Lcap)
            cols = {cid: a[li] for cid, a in lb.cols.items()}
            cols.update({cid: a[ri] for cid, a in rb.cols.items()})
            valids = {cid: v[li] for cid, v in lb.valids.items()}
            valids.update({cid: v[ri] for cid, v in rb.valids.items()})
            sel = lb.selection()[li] & rb.selection()[ri]
            return Batch(cols, valids, sel)

        return run

    def _c_join(self, plan: Join):
        if plan.kind == "cross":
            return self._c_join_cross(plan)
        if getattr(plan, "multi", False):
            return self._c_join_multi(plan)
        left_fn = self._compile_node(plan.left)
        right_fn, build_cap = self._compacted(
            plan.right, self._compile_node(plan.right))
        M = self._join_table_size(build_cap)
        probes = self._join_probes()
        lkeys, rkeys = plan.left_keys, plan.right_keys
        kind = plan.kind
        residual = plan.residual
        fid_ov = f"join_overflow_{len(self.flags)}"
        self.flags.append(fid_ov)
        fid_dup = None
        if kind in ("inner", "left"):
            # semi/anti only need existence: duplicate build keys are fine
            fid_dup = f"join_dup_{len(self.flags)}"
            self.flags.append(fid_dup)
        right_cols = [c for c in plan.right.out_cols()]

        null_aware = getattr(plan, "null_aware", False)
        jkb = getattr(plan, "key_bounds", None)
        semi_mids = self._semi_metrics(plan)
        # late materialisation: where the matches fit 1/COMPACT_RATIO of
        # the probe slots, the matched probe rows and their build rows are
        # compacted first and the build columns gathered into those slots
        k = self._join_compact_k(plan)
        compact = None if k is None else self._compaction(plan, k)
        if kind in ("inner", "left"):
            self.join_gather_slots.append(self._capacity_of(plan))

        # direct addressing at tier 0 only: a build-overflow retry (stale
        # stats: live keys outside the analyzed domain) falls back to the
        # general hash table at tier 1
        direct = (getattr(plan, "direct_domain", None) is not None
                  and self.tier == 0 and len(rkeys) == 1
                  and not self.no_direct)
        direct_lo = getattr(plan, "direct_lo", 0)
        direct_domain = getattr(plan, "direct_domain", 0)
        fid_pack = None
        if (not direct and jkb is not None
                and self._nid(plan) not in self.pack_disabled
                and join_ops.join_pack_bits(jkb) is not None):
            fid_pack = f"pack_overflow_{len(self.flags)}"
            self.flags.append(fid_pack)
            self.flag_packs[fid_pack] = self._nid(plan)
        else:
            jkb = None

        def run(ctx):
            from jax import lax

            lb = left_fn(ctx)
            rb = right_fn(ctx)
            rspecs = self._key_specs(rb, rkeys)
            lspecs = self._key_specs(lb, lkeys)
            if direct:
                table = join_ops.build_direct(
                    rspecs[0], rb.selection(), direct_lo, direct_domain)
                matched, brow = join_ops.probe_direct(
                    table, lspecs[0], lb.selection(), direct_lo)
                walk_ov = jnp.zeros((), bool)
            else:
                table = join_ops.build(rspecs, rb.selection(), M, probes, jkb)
                matched, brow, walk_ov = join_ops.probe(
                    table, lspecs, lb.selection(), probes)
                if fid_pack is not None:
                    ctx["flags"].append((fid_pack, table.pack_viol))
            ctx["flags"].append((fid_ov, table.overflow | walk_ov))
            if fid_dup is not None:
                ctx["flags"].append((fid_dup, table.dup))
            cols = dict(lb.cols)
            valids = dict(lb.valids)
            sel = lb.selection()
            if kind == "inner":
                sel = sel & matched
            elif kind == "semi":
                sel = sel & matched
            elif kind == "anti" and null_aware:
                # NOT IN semantics: empty subquery -> everything qualifies;
                # otherwise NULL probe keys and any NULL subquery key
                # disqualify (result NULL -> filtered)
                rsel = rb.selection()
                def _gmax(b):
                    return lax.pmax(jnp.any(b).astype(jnp.int32), SEG_AXIS) > 0
                s_nonempty = _gmax(rsel)
                s_has_null = jnp.zeros((), bool)
                x_null = jnp.zeros_like(sel)
                for sp in rspecs:
                    if sp.valid is not None:
                        s_has_null = s_has_null | _gmax(rsel & ~sp.valid)
                for sp in lspecs:
                    if sp.valid is not None:
                        x_null = x_null | ~sp.valid
                qualify = jnp.where(s_nonempty,
                                    ~x_null & ~matched & ~s_has_null, True)
                sel = sel & qualify
            elif kind == "anti":
                sel = sel & ~matched
            if compact is not None:
                cols, valids, sel = compact(
                    ctx, {**cols, BUILD_ROW: brow}, valids, sel)
                brow, matched = cols.pop(BUILD_ROW), sel
            if kind in ("inner", "left"):
                bcols = {c.id: rb.cols[c.id] for c in right_cols}
                bvalids = {c.id: rb.valids.get(c.id) for c in right_cols}
                g_cols, g_valids = join_ops.gather_build_columns(bcols, bvalids, brow, matched)
                cols.update(g_cols)
                valids.update(g_valids)
            out = Batch(cols, valids, sel)
            if residual is not None:
                mask = Evaluator(out, self.consts).predicate(residual)
                if kind == "left":
                    # residual only disqualifies the match, not the row
                    newm = matched & mask
                    for c in right_cols:
                        out.valids[c.id] = out.valids[c.id] & newm
                else:
                    out = out.with_sel(out.selection() & mask)
            if semi_mids is not None:
                # the direct table holds a key once a used slot, the
                # sorted one once a run head
                took, keys = ((table.live, table.used) if direct
                              else (table.n_live, table.head))
                self._semi_counts(ctx, semi_mids, took, keys, lb, out)
            return out

        return run

    def _semi_metrics(self, plan: Join) -> tuple | None:
        """A semi or anti join's SEMI_COUNTERS, registered as program
        metrics -> their names; None for another kind of join."""
        if plan.kind not in ("semi", "anti"):
            return None
        n = len(self.metrics)
        names = tuple(f"{c}_{n + i}" for i, c in enumerate(SEMI_COUNTERS))
        self.metrics.extend(names)
        return names

    @staticmethod
    def _semi_counts(ctx, names: tuple, took, keys, lb: Batch,
                     out: Batch) -> None:
        """Report a semi or anti join's counts: `took` the build rows its
        table took (a mask, or their number), `keys` a mask of one of them
        a distinct key, `lb` the probe batch, `out` the join's output."""
        def count(mask):
            return jnp.sum(mask.astype(jnp.int32))
        n_took = took if took.ndim == 0 else count(took)
        ctx["metrics"].extend(zip(names, (
            n_took, n_took - count(keys), count(lb.selection()),
            count(out.selection()))))

    def _join_multi_expand_cap(self, plan: Join) -> int:
        """A multi join's pair-EXPANSION capacity (`out_cap`), said once.
        Inner/left: the expansion IS the node's output, so `_capacity_of`
        sizes it — the exact-total retry hint, else 1.5 x the planner's
        output estimate (est_rows = |L||R|/NDV), pow2, x4 a tier.
        Semi/anti (below): the output is probe-shaped (`_capacity_of`
        gives the probe capacity), but the matched-pair expansion needs
        its own slot count — the exact-total retry hint, else the
        planner's stats-driven pair estimate (expand_est, the same
        |L||R|/NDV), else a blind multiple of the probe capacity.
        pow2-bucketed for shape-stable executable reuse
        (shape_signature walks this too)."""
        if plan.kind not in ("semi", "anti"):
            return self._capacity_of(plan)
        probe_cap0 = self._capacity_of(plan.left)
        if self._nid(plan) in self.cap_overrides:
            out_cap = _pow2(max(int(self.cap_overrides[self._nid(plan)]), 64))
        else:
            est = getattr(plan, "expand_est", None)
            if est:
                if plan.locus is not None and plan.locus.is_partitioned \
                        and self.nseg > 1:
                    est /= self.nseg
                out_cap = _pow2(int(est * 1.5) + 64)
            else:
                out_cap = _pow2(probe_cap0 * 2 + 64)
        return int(out_cap * (4 ** self.tier))

    def _c_join_multi(self, plan: Join):
        """Duplicate-capable join via CSR expansion: inner/left emit the
        matched pairs; semi/anti reduce the pairs back to PROBE rows with
        an any-match scatter — the shape EXISTS correlation with residual
        predicates needs (a probe row qualifies iff ANY duplicate build
        row passes equality AND the residual; nodeSubplan's hashed-EXISTS
        with non-hashable quals)."""
        left_fn = self._compile_node(plan.left)
        right_fn = self._compile_node(plan.right)
        build_cap = self._capacity_of(plan.right)
        M = self._join_table_size(build_cap)
        out_cap = self._join_multi_expand_cap(plan)
        probes = self._join_probes()
        lkeys, rkeys = plan.left_keys, plan.right_keys
        kind = plan.kind
        residual = plan.residual
        fid_ov = f"join_overflow_{len(self.flags)}"
        self.flags.append(fid_ov)
        fid_exp = f"join_expand_overflow_{len(self.flags)}"
        self.flags.append(fid_exp)
        mid_total = f"join_expand_total_{len(self.metrics)}"
        self.metrics.append(mid_total)
        # overflow retry can size from the exact reported cardinality
        self.flag_caps[fid_exp] = (self._nid(plan), mid_total)
        # probe rows a LEFT join null-extends: one more reported count
        mid_null = None
        if plan.kind == "left":
            mid_null = f"join_null_extended_{len(self.metrics)}"
            self.metrics.append(mid_null)
        # the join_expand_* counters read both beside the out_cap given
        self.expand_caps[mid_total] = (out_cap, mid_null)
        if kind in ("inner", "left"):
            self.join_gather_slots.append(out_cap)
        semi_mids = self._semi_metrics(plan)
        left_cols = [c for c in plan.left.out_cols()]
        right_cols = [c for c in plan.right.out_cols()]
        jkb = getattr(plan, "key_bounds", None)
        fid_pack = None
        if (jkb is not None and self._nid(plan) not in self.pack_disabled
                and join_ops.join_pack_bits(jkb) is not None):
            fid_pack = f"pack_overflow_{len(self.flags)}"
            self.flags.append(fid_pack)
            self.flag_packs[fid_pack] = self._nid(plan)
        else:
            jkb = None

        def pair_batch(lb, rb, prow, brow, matched, sel) -> Batch:
            """Both sides' columns gathered to the expansion's slots."""
            cols, valids = {}, {}
            for c in left_cols:
                cols[c.id] = lb.cols[c.id][prow]
                v = lb.valids.get(c.id)
                if v is not None:
                    valids[c.id] = v[prow]
            for c in right_cols:
                cols[c.id] = rb.cols[c.id][brow]
                v = rb.valids.get(c.id)
                gv = v[brow] if v is not None else jnp.ones_like(matched)
                valids[c.id] = gv & matched
            return Batch(cols, valids, sel)

        def pairs(ctx, lb, rb, present, prow, brow, matched):
            """The node's output from the expansion's slots."""
            P = lb.selection().shape[0]   # probe-side capacity
            if kind in ("semi", "anti"):
                # evaluate the residual on the PAIR batch, then reduce to
                # per-probe-row existence
                keep = present & matched
                if residual is not None:
                    pair = pair_batch(lb, rb, prow, brow, matched, keep)
                    keep = keep & Evaluator(pair, self.consts).predicate(residual)
                any_kept = jnp.zeros((P + 1,), bool).at[
                    jnp.where(present, prow, P)].max(keep)[:P]
                lsel = lb.selection()
                sel2 = (lsel & any_kept if kind == "semi"
                        else lsel & ~any_kept)
                return Batch(dict(lb.cols), dict(lb.valids), sel2)
            sel = present if kind == "left" else (present & matched)
            out = pair_batch(lb, rb, prow, brow, matched, sel)
            null_row = present & ~matched   # LEFT: probe rows with no match
            if residual is not None:
                mask = Evaluator(out, self.consts).predicate(residual)
                if kind == "left":
                    # per-match disqualification over duplicate builds
                    # (TPC-H Q13 shape): a pair failing the residual drops
                    # its output row — UNLESS the probe row then has no
                    # surviving pair, in which case its FIRST expanded row
                    # becomes the single null-extended row
                    keep = matched & mask
                    K = keep.shape[0]
                    any_kept = jnp.zeros((P + 1,), bool).at[
                        jnp.where(present, prow, P)].max(keep)
                    first = jnp.concatenate(
                        [jnp.ones((min(K, 1),), bool), prow[1:] != prow[:-1]]) \
                        if K > 1 else jnp.ones((K,), bool)
                    null_row = present & first & ~any_kept[prow]
                    out = out.with_sel(present & (keep | null_row))
                    for c in right_cols:
                        out.valids[c.id] = out.valids[c.id] & keep
                else:
                    out = out.with_sel(out.selection() & mask)
            if mid_null is not None:
                ctx["metrics"].append(
                    (mid_null, jnp.sum(null_row.astype(jnp.int64))))
            return out

        def run(ctx):
            lb = left_fn(ctx)
            rb = right_fn(ctx)
            table = join_ops.build_multi(
                self._key_specs(rb, rkeys), rb.selection(), M, probes, jkb)
            # the walk, then the expansion under its own scope (join-expand)
            (present, prow, brow, matched, expand_ov, walk_ov,
             total) = join_ops.probe_multi(
                table, self._key_specs(lb, lkeys), lb.selection(), probes,
                out_cap, left_outer=(kind == "left"))
            if fid_pack is not None:
                ctx["flags"].append((fid_pack, table.pack_viol))
            # walk overflow rides the table flag (tier retry grows M/hop
            # bound); expand overflow rides its own flag whose retry hint
            # sizes out_cap from `total`
            ctx["flags"].append((fid_ov, table.base.overflow | walk_ov))
            ctx["flags"].append((fid_exp, expand_ov))
            ctx["metrics"].append((mid_total, total))
            with jax.named_scope(JOIN_EXPAND):
                out = pairs(ctx, lb, rb, present, prow, brow, matched)
            if semi_mids is not None:
                self._semi_counts(ctx, semi_mids, table.n_live, table.head,
                                  lb, out)
            return out

        return run

    # ---- aggregation ---------------------------------------------------
    def _c_aggregate(self, plan: Aggregate):
        child_fn = self._compile_node(plan.child)
        dense = self._dense_domains(plan) if plan.group_keys else None
        use_sort = bool(plan.group_keys) and dense is None
        if dense is not None:
            M = 1
            for dom in dense:
                M *= dom
        else:
            M = 1
        child_cap = out_cap = fid = mid = None
        if use_sort:
            child_fn, child_cap = self._compacted(plan.child, child_fn)
            out_cap = self._capacity_of(plan)
            # every sort-based aggregate reports its exact group count: the
            # agg_sort_groups / agg_sort_capacity counters read it beside
            # the out_cap it was given (how full the group table ran)
            mid = f"agg_groups_{len(self.metrics)}"
            self.metrics.append(mid)
            self.agg_caps[mid] = (out_cap, child_cap)
            if agg_ops.group_starts_direct(out_cap, child_cap):
                self.agg_direct.add(mid)
        if use_sort and out_cap < child_cap:
            # output capacity below the theoretical max: group count can
            # overflow it; the device's exact count sizes the retry
            fid = f"agg_overflow_{len(self.flags)}"
            self.flags.append(fid)
            self.flag_caps[fid] = (self._nid(plan), mid)
        keys = plan.group_keys
        aggs = plan.aggs
        phase = plan.phase
        # packed single-operand group sort from ANALYZE key bounds
        key_bounds = getattr(plan, "key_bounds", None)
        fid_pack = None
        if key_bounds is None or agg_ops.pack_bits(key_bounds) is None:
            key_bounds = None
        # keys that do not pack into one word sort by a hash word of them
        # (ops/agg.group_sort); like a bound that no longer holds, a hash
        # shared by two keys re-runs this node with neither
        reduced = use_sort and self._nid(plan) not in self.pack_disabled
        if reduced:
            fid_pack = f"pack_overflow_{len(self.flags)}"
            self.flags.append(fid_pack)
            self.flag_packs[fid_pack] = self._nid(plan)
        if not reduced:
            key_bounds = None

        def run(ctx):
            b = child_fn(ctx)
            sel = b.selection()
            gid = None
            perm = None
            used = None
            meta0 = {}
            cols, valids = {}, {}
            if keys and dense is not None:
                kspecs = self._key_specs(b, [e for _, e in keys])
                gid, _ = agg_ops.dense_gid(kspecs, dense, sel)
                decoded = agg_ops.dense_decode_keys(kspecs, dense, M)
                tkeys = [code for code, _ in decoded]
                tvalids = [valid for _, valid in decoded]
            elif keys:
                # sort-based high-cardinality grouping (execHHashagg spill
                # regime analog): sort by keys, cumsum-span reduce into the
                # group table; slot g's keys gather from its first row
                kspecs = self._key_specs(b, [e for _, e in keys])
                perm, boundary, sel_sorted, pack_viol = agg_ops.group_sort(
                    kspecs, sel, key_bounds, hashed=reduced)
                if fid_pack is not None:
                    ctx["flags"].append(
                        (fid_pack, jnp.zeros((), bool) if pack_viol is None
                         else pack_viol))
                tkeys, tvalids = [], []
            else:
                used = jnp.ones((1,), dtype=bool)
                tkeys, tvalids = [], []

            Mx = M
            ev = Evaluator(b, self.consts)
            for (ci, _), tk, tv in zip(keys, tkeys, tvalids):
                cols[ci.id] = tk
                if tv is not None:
                    valids[ci.id] = tv

            meta = {}

            def do_agg(specs):
                if gid is not None:
                    # "@used" rides the same pass: per-group live-row
                    # presence without the extra [n, D] broadcast scan
                    specs2 = list(specs) + [
                        agg_ops.AggSpec("@used", "count_star", None, None)]
                    vals, avalids = agg_ops.dense_aggregate(
                        gid, Mx, specs2, sel)
                    meta0["used"] = vals.pop("@used") > 0
                    avalids.pop("@used", None)
                    return vals, avalids
                if perm is not None:
                    ps = [agg_ops.AggSpec(
                        s.name, s.func,
                        None if s.values is None else s.values[perm],
                        None if s.valid is None else s.valid[perm],
                        s.decimal_scale) for s in specs]
                    vals, avalids, meta["srcpos"], meta["total"] = \
                        agg_ops.sorted_group_aggregate(
                            boundary, sel_sorted, ps, out_cap)
                    return vals, avalids
                return agg_ops.scalar_aggregate(specs, sel)

            if phase in ("single", "partial"):
                specs = []
                post = []   # (out id, kind, ...) finalization steps
                for ci, a in aggs:
                    arg_v, arg_valid, scale = None, None, 0
                    if a.arg is not None:
                        arg_v, arg_valid = ev.value(a.arg)
                        if a.arg.type.kind is T.Kind.DECIMAL:
                            scale = a.arg.type.scale
                    if phase == "single":
                        specs.append(agg_ops.AggSpec(ci.id, a.func, arg_v, arg_valid, scale))
                    else:
                        if a.func in ("count", "count_star"):
                            specs.append(agg_ops.AggSpec(ci.id + "@c", a.func, arg_v, arg_valid))
                        elif a.func == "sum":
                            specs.append(agg_ops.AggSpec(ci.id + "@s", "sum", arg_v, arg_valid))
                        elif a.func == "avg":
                            specs.append(agg_ops.AggSpec(ci.id + "@s", "sum", arg_v, arg_valid))
                            specs.append(agg_ops.AggSpec(ci.id + "@c", "count", arg_v, arg_valid))
                        elif a.func in ("min", "max"):
                            specs.append(agg_ops.AggSpec(ci.id + "@m", a.func, arg_v, arg_valid))
                vals, avalids = do_agg(specs)
                for name, v in vals.items():
                    cols[name] = v
                    if avalids.get(name) is not None:
                        valids[name] = avalids[name]
            else:  # final: merge partial states arriving in b
                specs = []
                finals = []
                for ci, a in aggs:
                    if a.func in ("count", "count_star"):
                        specs.append(agg_ops.AggSpec(
                            ci.id, "sum", b.cols[ci.id + "@c"], b.valids.get(ci.id + "@c")))
                        finals.append((ci, "count"))
                    elif a.func == "sum":
                        specs.append(agg_ops.AggSpec(
                            ci.id, "sum", b.cols[ci.id + "@s"], b.valids.get(ci.id + "@s")))
                        finals.append((ci, "sum"))
                    elif a.func == "avg":
                        specs.append(agg_ops.AggSpec(
                            ci.id + "@s", "sum", b.cols[ci.id + "@s"], b.valids.get(ci.id + "@s")))
                        specs.append(agg_ops.AggSpec(
                            ci.id + "@c", "sum", b.cols[ci.id + "@c"], b.valids.get(ci.id + "@c")))
                        scale = a.arg.type.scale if (a.arg is not None and
                                                     a.arg.type.kind is T.Kind.DECIMAL) else 0
                        finals.append((ci, "avg", scale))
                    elif a.func in ("min", "max"):
                        specs.append(agg_ops.AggSpec(
                            ci.id, a.func, b.cols[ci.id + "@m"], b.valids.get(ci.id + "@m")))
                        finals.append((ci, a.func))
                vals, avalids = do_agg(specs)
                for f in finals:
                    ci = f[0]
                    if f[1] == "avg":
                        s = vals[ci.id + "@s"].astype(jnp.float64)
                        c = vals[ci.id + "@c"].astype(jnp.float64)
                        res = s / jnp.where(c == 0, 1.0, c)
                        if f[2]:
                            res = res / (10.0 ** f[2])
                        cols[ci.id] = res
                        valids[ci.id] = vals[ci.id + "@c"] > 0
                    elif f[1] == "count":
                        cols[ci.id] = vals[ci.id].astype(jnp.int64)
                    else:
                        cols[ci.id] = vals[ci.id]
                        if avalids.get(ci.id) is not None:
                            valids[ci.id] = avalids[ci.id]
            if gid is not None:
                used = meta0["used"]
            if perm is not None:
                # group g's key values gather from its first sorted row
                rep = perm[meta["srcpos"]]
                for (ci, _), sp in zip(keys, kspecs):
                    cols[ci.id] = sp.values[rep]
                    if sp.valid is not None:
                        valids[ci.id] = sp.valid[rep]
                total = meta["total"]
                used = jnp.arange(out_cap, dtype=jnp.int32) < total
                if fid is not None:
                    ctx["flags"].append((fid, total > out_cap))
                ctx["metrics"].append((mid, total.astype(jnp.int64)))
            return Batch(cols, valids, used)

        return run

    def _c_partialstate(self, plan: PartialState):
        return self._compile_node(plan.child)

    # ---- motion --------------------------------------------------------
    def _c_motion(self, plan: Motion):
        child_fn = self._compile_node(plan.child)
        if plan.kind is MotionKind.GATHER:
            raise AssertionError("nested gather")
        if self._motion_is_identity(plan):
            return child_fn
        nseg = self.nseg
        if plan.kind is MotionKind.BROADCAST:
            def run(ctx):
                b = child_fn(ctx)
                arrs = dict(b.cols)
                for name, v in b.valids.items():
                    arrs[VALID_PREFIX + name] = v
                recv, precv = motion_ops.broadcast(arrs, b.selection())
                cols = {k: v for k, v in recv.items() if not k.startswith(VALID_PREFIX)}
                valids = {k[len(VALID_PREFIX):]: v for k, v in recv.items()
                          if k.startswith(VALID_PREFIX)}
                return Batch(cols, valids, precv)

            return run

        # REDISTRIBUTE
        child_cap = self._capacity_of(plan.child)
        C = self._motion_bucket(child_cap)
        # sub-exchange split (motion_pipeline_buckets): capacity is
        # pow2(>=64) x 4^tier, so any pow2 bucket count <= 64 divides it;
        # redistribute() itself guards the uneven case back to monolithic
        nb = max(int(getattr(self.s, "motion_pipeline_buckets", 1)), 1)
        hash_exprs = plan.hash_exprs
        fid = f"motion_overflow_{len(self.flags)}"
        self.flags.append(fid)

        if plan.range_spec is not None:
            # range repartition by sampled splitters (the distributed
            # sample-sort routing step): each segment samples S evenly
            # spaced values of its locally sorted keys, the gathered
            # sample sorts globally, and nseg-1 splitters route every row
            # so equal keys co-locate and segments own contiguous ranges.
            # Deterministic and SPMD-identical — every segment computes
            # the same splitters from the same all_gather.
            spec = plan.range_spec
            S = max(int(getattr(self.s, "window_range_sample", 64)), 8)

            def run_range(ctx):
                from jax import lax

                b = child_fn(ctx)
                sel = b.selection()
                ev = Evaluator(b, self.consts)
                v, valid = ev.value(spec["expr"])
                enc = sort_ops.encode_key64(v, spec["desc"], spec["kind"])
                MAXU = jnp.uint64(0xFFFFFFFFFFFFFFFF)
                if valid is not None:
                    # live NULL keys are all peers on the leading key:
                    # route them together to the end their placement puts
                    # them at
                    enc = jnp.where(valid, enc,
                                    jnp.uint64(0) if spec["nulls_first"]
                                    else MAXU)
                dead = ~sel
                n = sel.shape[0]
                enc_sorted = lax.sort(
                    (dead.astype(jnp.uint8), jnp.where(dead, MAXU, enc)),
                    num_keys=2)[1]
                live = jnp.sum((~dead).astype(jnp.int64))
                take = jnp.clip(
                    (jnp.arange(S, dtype=jnp.int64) * live) // S,
                    0, n - 1).astype(jnp.int32)
                samp = jnp.where(live > 0, enc_sorted[take], MAXU)
                g = lax.sort(
                    lax.all_gather(samp, SEG_AXIS).reshape(nseg * S))
                splitters = g[jnp.asarray(
                    [(i + 1) * (nseg * S) // nseg - 1
                     for i in range(nseg - 1)], dtype=jnp.int32)]
                # count of splitters strictly below enc: equal keys land
                # on the same destination segment, always
                dest = jnp.searchsorted(
                    splitters, enc, side="left").astype(jnp.int32)
                arrs = dict(b.cols)
                for name, vv in b.valids.items():
                    arrs[VALID_PREFIX + name] = vv
                recv, precv, overflow = motion_ops.redistribute(
                    arrs, sel, dest, nseg, C, nbuckets=nb)
                ctx["flags"].append((fid, overflow))
                cols = {k: a for k, a in recv.items()
                        if not k.startswith(VALID_PREFIX)}
                valids = {k[len(VALID_PREFIX):]: a for k, a in recv.items()
                          if k.startswith(VALID_PREFIX)}
                return Batch(cols, valids, precv)

            return run_range

        def run(ctx):
            b = child_fn(ctx)
            specs = self._key_specs(b, hash_exprs)
            h = hashing.row_hash([
                hashing.column_hash(s.values, s.valid, s.type, text_lut=s.hash_lut)
                for s in specs
            ])
            dest = hashing.segment_of(h, nseg)
            arrs = dict(b.cols)
            for name, v in b.valids.items():
                arrs[VALID_PREFIX + name] = v
            recv, precv, overflow = motion_ops.redistribute(
                arrs, b.selection(), dest, nseg, C, nbuckets=nb)
            ctx["flags"].append((fid, overflow))
            cols = {k: v for k, v in recv.items() if not k.startswith(VALID_PREFIX)}
            valids = {k[len(VALID_PREFIX):]: v for k, v in recv.items()
                      if k.startswith(VALID_PREFIX)}
            return Batch(cols, valids, precv)

        return run

    # ---- window --------------------------------------------------------
    def _c_window(self, plan: Window):
        from greengage_tpu.ops import window as win_ops

        if getattr(plan, "global_mode", False):
            return self._c_window_global(plan)
        child_fn = self._compile_node(plan.child)
        cap = self._capacity_of(plan.child)
        pkeys = plan.partition_keys
        okeys = plan.order_keys
        wfuncs = plan.wfuncs

        def run(ctx):
            b = child_fn(ctx)
            # sort by (partition, order); dead rows go to the end
            skeys = self._sort_keys(
                b, [(e, False, None) for e in pkeys] + list(okeys))
            perm, sel_sorted, _ = sort_ops.sort_batch(skeys, b.selection(), cap)
            cols, valids = sort_ops.apply_perm(b.cols, b.valids, perm)
            sb = Batch(cols, valids, sel_sorted)
            ev = Evaluator(sb, self.consts)

            def eq_prev(exprs):
                eq = jnp.ones((cap,), dtype=bool)
                for e in exprs:
                    v, valid = ev.value(e)
                    same = v[1:] == v[:-1]
                    if valid is not None:
                        same = (same & valid[1:] & valid[:-1]) | (
                            ~valid[1:] & ~valid[:-1])
                    eq = eq & jnp.concatenate(
                        [jnp.zeros((1,), bool), same])
                return eq

            part_eq = eq_prev(pkeys) if pkeys else jnp.concatenate(
                [jnp.zeros((1,), bool), jnp.ones((cap - 1,), bool)])
            # dead rows (parked at the end by the sort) must always BREAK
            # a group: padded buffer values can compare equal to the last
            # live row, silently extending its peer/partition end into
            # the dead region (ops/window.py documents both arrays False
            # at dead rows — enforce it)
            live_pair = sel_sorted & jnp.concatenate(
                [jnp.zeros((1,), bool), sel_sorted[:-1]])
            part_eq = part_eq & live_pair
            peer_eq = part_eq & (eq_prev([e for e, _, _ in okeys])
                                 if okeys else jnp.ones((cap,), bool))

            funcs = []
            for ci, fname, arg, ordered, param in wfuncs:
                vals, valid, scale = None, None, 0
                if arg is not None:
                    vals, valid = ev.value(arg)
                    if arg.type.kind is T.Kind.DECIMAL:
                        scale = arg.type.scale
                funcs.append(win_ops.WinFunc(ci.id, fname, vals, valid,
                                             scale, ordered, param))
            wvals, wvalids = win_ops.compute(part_eq, peer_eq, sel_sorted,
                                             funcs, frame=plan.frame)
            out_c = dict(sb.cols)
            out_v = dict(sb.valids)
            for ci, *_ in wfuncs:
                out_c[ci.id] = wvals[ci.id]
                if wvalids.get(ci.id) is not None:
                    out_v[ci.id] = wvalids[ci.id]
            return Batch(out_c, out_v, sel_sorted)

        return run

    def _c_window_global(self, plan: Window):
        """Distributed GLOBAL window (no PARTITION BY, no ORDER BY): the
        whole table is one partition, so every function reduces to a
        cross-mesh collective — rows never move (the planner previously
        funneled the entire table to one chip through a constant-key
        redistribute; VERDICT r3 weak #9). row_number() is the local
        live-row prefix count plus an exclusive scan of per-segment
        totals; sum/count/avg/min/max are psum/pmin/pmax of local
        partials broadcast back to every row."""
        child_fn = self._compile_node(plan.child)
        cap = self._capacity_of(plan.child)
        wfuncs = plan.wfuncs
        nseg = self.nseg
        if plan.global_mode == "ordered":
            return self._c_window_global_ordered(plan, child_fn, cap)
        if plan.global_mode == "range":
            return self._c_window_global_range(plan, child_fn, cap)

        def run(ctx):
            from jax import lax

            b = child_fn(ctx)
            sel = b.selection()
            ev = Evaluator(b, self.consts)
            seg = lax.axis_index(SEG_AXIS)
            out_c = dict(b.cols)
            out_v = dict(b.valids)
            for ci, fname, arg, _ordered, _param in wfuncs:
                vals = valid = None
                scale = 0
                if arg is not None:
                    vals, valid = ev.value(arg)
                    if arg.type.kind is T.Kind.DECIMAL:
                        scale = arg.type.scale
                lv = sel if valid is None else (sel & valid)
                if fname in ("first_value", "last_value"):
                    # whole-frame semantics (legal without ORDER BY, PG):
                    # the first/last live ROW of the one global partition
                    # in (segment, row) order — its value even when NULL
                    va = valid if valid is not None \
                        else jnp.ones((cap,), bool)
                    if fname == "first_value":
                        li = jnp.argmax(sel)
                    else:
                        li = cap - 1 - jnp.argmax(sel[::-1])
                    g_has = lax.all_gather(jnp.any(sel), SEG_AXIS)
                    g_val = lax.all_gather(vals[li], SEG_AXIS)
                    g_ok = lax.all_gather(va[li], SEG_AXIS)
                    if fname == "first_value":
                        pick = jnp.argmax(g_has)
                    else:
                        pick = nseg - 1 - jnp.argmax(g_has[::-1])
                    out_c[ci.id] = jnp.broadcast_to(g_val[pick], (cap,))
                    out_v[ci.id] = jnp.broadcast_to(
                        g_ok[pick] & jnp.any(g_has), (cap,))
                    continue
                if fname == "row_number":
                    local = jnp.cumsum(sel.astype(jnp.int64))
                    counts = lax.all_gather(
                        jnp.sum(sel.astype(jnp.int64)), SEG_AXIS)
                    offset = jnp.sum(jnp.where(
                        jnp.arange(nseg, dtype=jnp.int64) < seg, counts, 0))
                    out_c[ci.id] = local + offset
                    out_v.pop(ci.id, None)
                    continue
                if fname in ("count",):
                    total = lax.psum(jnp.sum(lv.astype(jnp.int64)), SEG_AXIS)
                    out_c[ci.id] = jnp.broadcast_to(total, (cap,))
                    out_v.pop(ci.id, None)
                    continue
                if fname in ("sum", "avg"):
                    acc = (jnp.float64 if vals.dtype.kind == "f"
                           else jnp.int64)
                    s = lax.psum(
                        jnp.sum(jnp.where(lv, vals.astype(acc), acc(0))),
                        SEG_AXIS)
                    c = lax.psum(jnp.sum(lv.astype(jnp.int64)), SEG_AXIS)
                    if fname == "sum":
                        out_c[ci.id] = jnp.broadcast_to(s, (cap,))
                    else:
                        a = (s.astype(jnp.float64)
                             / jnp.where(c == 0, 1, c).astype(jnp.float64))
                        if scale:
                            a = a / (10.0 ** scale)
                        out_c[ci.id] = jnp.broadcast_to(a, (cap,))
                    out_v[ci.id] = jnp.broadcast_to(c > 0, (cap,))
                    continue
                # min / max (same identity-fill rule as ops/window.py)
                if vals.dtype.kind == "f":
                    ident = jnp.array(jnp.inf if fname == "min" else -jnp.inf,
                                      vals.dtype)
                else:
                    info = jnp.iinfo(vals.dtype)
                    ident = jnp.array(info.max if fname == "min"
                                      else info.min, vals.dtype)
                filled = jnp.where(lv, vals, ident)
                red = jnp.min(filled) if fname == "min" else jnp.max(filled)
                glob = (lax.pmin(red, SEG_AXIS) if fname == "min"
                        else lax.pmax(red, SEG_AXIS))
                c = lax.psum(jnp.sum(lv.astype(jnp.int64)), SEG_AXIS)
                out_c[ci.id] = jnp.broadcast_to(glob, (cap,))
                out_v[ci.id] = jnp.broadcast_to(c > 0, (cap,))
            return Batch(out_c, out_v, sel)

        return run

    def _c_window_global_ordered(self, plan: Window, child_fn, cap: int):
        """Distributed GLOBAL ranking family (row_number/rank/dense_rank/
        ntile/lag/lead/first_value/last_value) over integer/date/decimal/
        float ORDER BY keys: each row's GLOBAL position and the global
        row count are computed IN PLACE — per segment, encode the keys
        order-preservingly into one uint64, locally sort, all_gather the
        sorted runs [nseg, cap] + live counts, and per row sum
        searchsorted counts across segments. ntile(k) is then arithmetic
        on (position, count); lag/lead/first/last resolve position ±
        offset via a lookup into the globally sorted gathered value runs.
        No funnel, no row motion: ~8B x rows of gathered keys (plus one
        value run per positional argument) vs moving every row AND its
        payload to one chip (reference shape: nodeWindowAgg.c over a
        distributed tuplesort).

        Encodings (planner._ordered_global_spec):
          packed — every key maps to (null_bit, value - lo) fields using
            EXACT zone-map bounds; DESC complements within the field,
            NULLS FIRST/LAST picks the null bit polarity. NULLs are
            ordinary key values here, so one code path serves all shapes.
          full64 — one key, no bounds: sign-flip encoding over the full
            64-bit domain; NULL keys form a separate runtime class
            counted via psum (all NULLs tie; placed per nulls_first).
        row_number() breaks ties deterministically by (segment, local
        sorted position); dense_rank counts distinct keys via a global
        two-key sort of the gathered runs + boundary cumsum."""
        from greengage_tpu.ops import window as win_ops

        wfuncs = plan.wfuncs
        nseg = self.nseg
        spec = plan.gkey_spec
        need_dense = any(f[1] == "dense_rank" for f in wfuncs)
        VALUE_FUNCS = ("lag", "lead", "first_value", "last_value")
        need_values = any(f[1] in VALUE_FUNCS for f in wfuncs)

        def run(ctx):
            from jax import lax

            b = child_fn(ctx)
            sel = b.selection()
            ev = Evaluator(b, self.consts)
            U1 = jnp.uint64(1)
            if spec["mode"] == "packed":
                shift = 64
                enc = jnp.zeros((cap,), jnp.uint64)
                for f in spec["fields"]:
                    v, valid = ev.value(f["expr"])
                    v64 = v.astype(jnp.int64)
                    ve = ((jnp.int64(f["hi"]) - v64) if f["desc"]
                          else (v64 - jnp.int64(f["lo"])))
                    # clamp defends against out-of-zone garbage at dead
                    # rows (fillers); live values are inside by soundness
                    ve = jnp.clip(ve, 0, (1 << f["bits"]) - 1).astype(jnp.uint64)
                    if valid is None:
                        # non-null bit: 1 under NULLS FIRST (nulls=0
                        # sort first), 0 under NULLS LAST
                        fe = ((U1 << jnp.uint64(f["bits"])) | ve
                              if f["nulls_first"] else ve)
                    else:
                        isnull = ~valid
                        nn_bit = U1 if f["nulls_first"] else jnp.uint64(0)
                        nl_bit = jnp.uint64(0) if f["nulls_first"] else U1
                        flag = jnp.where(isnull, nl_bit, nn_bit)
                        fe = (flag << jnp.uint64(f["bits"])) | jnp.where(
                            isnull, jnp.uint64(0), ve)
                    shift -= f["bits"] + 1
                    enc = enc | (fe << jnp.uint64(shift))
                isnull_cls = jnp.zeros((cap,), bool)
                nulls_first = False
                dead = ~sel
            else:                                   # full64, one key
                v, valid = ev.value(spec["expr"])
                enc = sort_ops.encode_key64(v, spec["desc"],
                                            spec.get("kind", "int"))
                isnull_cls = (sel & ~valid) if valid is not None \
                    else jnp.zeros((cap,), bool)
                nulls_first = spec["nulls_first"]
                dead = ~sel | isnull_cls

            # dead rows park at the top of the sorted run (dead flag is
            # the primary sort key) and their counted contributions are
            # clamped away by the live counts below
            enc_d = jnp.where(dead, jnp.uint64(0xFFFFFFFFFFFFFFFF), enc)
            rid = jnp.arange(cap, dtype=jnp.int32)
            _d, sorted_enc, sorted_rid = lax.sort(
                (dead.astype(jnp.uint8), enc_d, rid), num_keys=2,
                is_stable=True)
            live_n = jnp.sum((~dead).astype(jnp.int64))
            g_sorted = lax.all_gather(sorted_enc, SEG_AXIS)   # [nseg, cap]
            g_live = lax.all_gather(live_n, SEG_AXIS)         # [nseg]
            left = jax.vmap(
                lambda a: jnp.searchsorted(a, enc_d, side="left"))(g_sorted)
            right = jax.vmap(
                lambda a: jnp.searchsorted(a, enc_d, side="right"))(g_sorted)
            left = jnp.minimum(left, g_live[:, None])
            right = jnp.minimum(right, g_live[:, None])
            less_g = jnp.sum(left, axis=0)
            seg = lax.axis_index(SEG_AXIS)
            prior = jnp.arange(nseg)[:, None] < seg
            eq_prior = jnp.sum(jnp.where(prior, right - left, 0), axis=0)
            # local tie position (stable by original row order)
            pos = jnp.zeros((cap,), jnp.int32).at[sorted_rid].set(rid)
            first_eq = jnp.minimum(
                jnp.searchsorted(sorted_enc, enc_d, side="left"), live_n)
            local_eq_before = pos.astype(jnp.int64) - first_eq

            # NULL class (full64 only): all NULL-key rows tie; placed
            # before or after every valued row per nulls_first
            n_null_local = jnp.sum(isnull_cls.astype(jnp.int64))
            g_null = lax.all_gather(n_null_local, SEG_AXIS)   # [nseg]
            n_null_total = jnp.sum(g_null)
            total_valued = jnp.sum(g_live)
            null_prior_segs = jnp.sum(jnp.where(jnp.arange(nseg) < seg,
                                                g_null, 0))
            local_null_idx = jnp.cumsum(isnull_cls.astype(jnp.int64)) - 1
            valued_base = jnp.where(nulls_first, n_null_total, 0)
            null_base = jnp.where(nulls_first, 0, total_valued)

            # global 0-based position of every row (row_number semantics:
            # ties break by (segment, local sorted position)) and the
            # GLOBAL row count — ntile is pure arithmetic on these, and
            # lag/lead/first/last resolve position±offset via the lookup
            rn0 = jnp.where(
                isnull_cls,
                null_base + null_prior_segs + local_null_idx,
                valued_base + less_g + eq_prior + local_eq_before
            ).astype(jnp.int64)
            n_total = total_valued + n_null_total

            flat = flive = None
            if need_dense or need_values:
                flat = g_sorted.reshape(nseg * cap)
                flive = (jnp.arange(cap)[None, :] < g_live[:, None]) \
                    .reshape(nseg * cap)

            dense_b = total_distinct = None
            if need_dense:
                # distinct count: one global sort of the gathered runs by
                # (enc, live-first) + boundary flags on live key changes.
                # Dead entries carry 0xFF..FF; a LIVE max-value row sorts
                # before them (secondary key) so its boundary still counts
                s_enc, s_dead, s_live = lax.sort(
                    (flat, (~flive).astype(jnp.uint8), flive), num_keys=2,
                    is_stable=True)
                first = jnp.concatenate([
                    jnp.array([True]), s_enc[1:] != s_enc[:-1]])
                d = (s_live & first).astype(jnp.int64)
                cum_excl = jnp.cumsum(d) - d
                idx = jnp.searchsorted(s_enc, enc_d, side="left")
                dense_b = cum_excl[jnp.clip(idx, 0, nseg * cap - 1)]
                total_distinct = jnp.sum(d)

            cum_null = jnp.cumsum(g_null)

            def make_lookup(arg):
                """-> lookup(p): the window argument's (value, valid) at
                GLOBAL position p. Valued positions read the globally
                sorted gathered value run — live entries occupy exactly
                [0, total_valued) in rank order, and the stable sort's
                seg-major tie order equals the rank tie-break (runs are
                locally sorted, flattened segment-major). full64
                NULL-class positions read a (segment, row)-ordered
                gathered run of the null-key rows."""
                vals, valid = ev.value(arg)
                va = valid if valid is not None else jnp.ones((cap,), bool)
                g_vs = lax.all_gather(
                    vals[sorted_rid], SEG_AXIS).reshape(nseg * cap)
                g_vv = lax.all_gather(
                    va[sorted_rid], SEG_AXIS).reshape(nseg * cap)
                _e, _d2, s_vals, s_valid = lax.sort(
                    (flat, (~flive).astype(jnp.uint8), g_vs, g_vv),
                    num_keys=2, is_stable=True)
                if spec["mode"] == "full64":
                    npos = jnp.where(
                        isnull_cls,
                        jnp.cumsum(isnull_cls.astype(jnp.int32)) - 1,
                        jnp.int32(cap))
                    g_nv = lax.all_gather(
                        jnp.zeros((cap + 1,), vals.dtype)
                        .at[npos].set(vals)[:cap], SEG_AXIS)   # [nseg,cap]
                    g_nvv = lax.all_gather(
                        jnp.zeros((cap + 1,), bool)
                        .at[npos].set(va)[:cap], SEG_AXIS)
                else:
                    g_nv = g_nvv = None

                def lookup(p):
                    q = jnp.clip(
                        jnp.where(nulls_first, p - n_null_total, p),
                        0, nseg * cap - 1)
                    val = s_vals[q]
                    ok = s_valid[q]
                    if g_nv is not None:
                        in_null = (p < n_null_total) if nulls_first \
                            else (p >= total_valued)
                        j = p if nulls_first else p - total_valued
                        sg = jnp.clip(
                            jnp.searchsorted(cum_null, j, side="right"),
                            0, nseg - 1)
                        loc = jnp.clip(j - (cum_null[sg] - g_null[sg]),
                                       0, cap - 1).astype(jnp.int32)
                        val = jnp.where(in_null, g_nv[sg, loc], val)
                        ok = jnp.where(in_null, g_nvv[sg, loc], ok)
                    return val, ok

                return lookup

            out_c = dict(b.cols)
            out_v = dict(b.valids)
            for ci, fname, arg, _ordered, param in wfuncs:
                if fname == "row_number":
                    out_c[ci.id] = rn0 + 1
                    out_v.pop(ci.id, None)
                    continue
                if fname == "rank":
                    out_c[ci.id] = jnp.where(
                        isnull_cls, null_base, valued_base + less_g) + 1
                    out_v.pop(ci.id, None)
                    continue
                if fname == "dense_rank":
                    has_nulls_first = (n_null_total > 0) & nulls_first
                    valued = dense_b + has_nulls_first.astype(jnp.int64)
                    nullv = jnp.where(nulls_first, 0, total_distinct)
                    nullv = jnp.broadcast_to(nullv, (cap,))
                    out_c[ci.id] = jnp.where(isnull_cls, nullv, valued) + 1
                    out_v.pop(ci.id, None)
                    continue
                if fname == "ntile":
                    out_c[ci.id] = win_ops.ntile_bucket(rn0, n_total, param)
                    out_v.pop(ci.id, None)
                    continue
                if fname in ("lag", "lead"):
                    out_c[ci.id], out_v[ci.id] = _lag_lead_lookup(
                        fname, param, rn0, n_total, make_lookup(arg), sel)
                    continue
                # first_value / last_value, default frame (RANGE
                # UNBOUNDED PRECEDING..CURRENT ROW): frame start is the
                # global partition start, frame end the row's last PEER
                lk = make_lookup(arg)
                if fname == "first_value":
                    p = jnp.zeros((cap,), jnp.int64)
                else:
                    eq_total = jnp.sum(right - left, axis=0)
                    p = jnp.where(
                        isnull_cls,
                        null_base + n_null_total - 1,
                        valued_base + less_g + eq_total - 1
                    ).astype(jnp.int64)
                val, vv = lk(p)
                out_c[ci.id] = val
                out_v[ci.id] = vv & sel
            return Batch(out_c, out_v, sel)

        return run

    def _c_window_global_range(self, plan: Window, child_fn, cap: int):
        """Global window over RANGE-repartitioned rows (the child is the
        sampled-splitter Redistribute, _c_motion): each segment owns a
        contiguous range of the leading ORDER BY key with equal keys
        co-located, so after a segment-local sort by the FULL key list
        the global order is simply the concatenation of the per-segment
        runs — peer groups never straddle a boundary. Rank family and
        dense_rank stitch with all-gathered per-segment counts, ntile is
        arithmetic on (global position, global count), running
        sum/count/avg/min/max add prior segments' totals, and
        lag/lead/first_value resolve cross-segment positions via a
        lookup into the all-gathered sorted runs. One balanced
        Redistribute where the planner used to funnel every row to one
        chip."""
        from greengage_tpu.ops import window as win_ops

        wfuncs = plan.wfuncs
        nseg = self.nseg
        okeys = plan.order_keys

        def run(ctx):
            from jax import lax

            b = child_fn(ctx)
            skeys = self._sort_keys(b, okeys)
            perm, sel_sorted, _ = sort_ops.sort_batch(
                skeys, b.selection(), cap)
            cols, valids = sort_ops.apply_perm(b.cols, b.valids, perm)
            sb = Batch(cols, valids, sel_sorted)
            ev = Evaluator(sb, self.consts)
            idx = jnp.arange(cap, dtype=jnp.int32)
            # peer boundaries among LIVE rows (dead rows park at the end
            # and always break a group — padded values can tie)
            eq = jnp.ones((cap,), bool)
            for e, _d, _nf in okeys:
                v, valid = ev.value(e)
                same = v[1:] == v[:-1]
                if valid is not None:
                    same = (same & valid[1:] & valid[:-1]) | (
                        ~valid[1:] & ~valid[:-1])
                eq = eq & jnp.concatenate([jnp.zeros((1,), bool), same])
            eq = eq & sel_sorted & jnp.concatenate(
                [jnp.zeros((1,), bool), sel_sorted[:-1]])
            peer_bound = ~eq
            peer_start = win_ops._starts(peer_bound, idx)
            peer_end = jnp.clip(win_ops._ends(peer_start, cap), 0, cap - 1)

            n_live = jnp.sum(sel_sorted.astype(jnp.int64))
            g_n = lax.all_gather(n_live, SEG_AXIS)           # [nseg]
            seg = lax.axis_index(SEG_AXIS)
            prior_mask = jnp.arange(nseg) < seg
            prior = jnp.sum(jnp.where(prior_mask, g_n, 0))
            n_total = jnp.sum(g_n)
            cum_n = jnp.cumsum(g_n)
            # live rows occupy the local prefix, so local index == local
            # rank and the global 0-based position is one offset away
            rn0 = idx.astype(jnp.int64) + prior

            def make_lookup(vals, va):
                g_vals = lax.all_gather(vals, SEG_AXIS)      # [nseg, cap]
                g_valid = lax.all_gather(va, SEG_AXIS)

                def lookup(p):
                    sg = jnp.clip(
                        jnp.searchsorted(cum_n, p, side="right"),
                        0, nseg - 1)
                    loc = jnp.clip(p - (cum_n[sg] - g_n[sg]),
                                   0, cap - 1).astype(jnp.int32)
                    return g_vals[sg, loc], g_valid[sg, loc]

                return lookup

            out_c = dict(sb.cols)
            out_v = dict(sb.valids)
            db_loc = jnp.cumsum((peer_bound & sel_sorted).astype(jnp.int64))
            for ci, fname, arg, _ordered, param in wfuncs:
                vals = valid = None
                scale = 0
                if arg is not None:
                    vals, valid = ev.value(arg)
                    if arg.type.kind is T.Kind.DECIMAL:
                        scale = arg.type.scale
                if fname == "row_number":
                    out_c[ci.id] = rn0 + 1
                    out_v.pop(ci.id, None)
                    continue
                if fname == "rank":
                    out_c[ci.id] = peer_start.astype(jnp.int64) + prior + 1
                    out_v.pop(ci.id, None)
                    continue
                if fname == "dense_rank":
                    g_d = lax.all_gather(db_loc[cap - 1], SEG_AXIS)
                    out_c[ci.id] = db_loc + jnp.sum(
                        jnp.where(prior_mask, g_d, 0))
                    out_v.pop(ci.id, None)
                    continue
                if fname == "ntile":
                    out_c[ci.id] = win_ops.ntile_bucket(rn0, n_total, param)
                    out_v.pop(ci.id, None)
                    continue
                if fname in ("lag", "lead"):
                    va = valid if valid is not None \
                        else jnp.ones((cap,), bool)
                    out_c[ci.id], out_v[ci.id] = _lag_lead_lookup(
                        fname, param, rn0, n_total,
                        make_lookup(vals, va), sel_sorted)
                    continue
                if fname in ("first_value", "last_value"):
                    va = valid if valid is not None \
                        else jnp.ones((cap,), bool)
                    if fname == "first_value":
                        # global partition start lives on the first
                        # non-empty segment
                        lk = make_lookup(vals, va)
                        val, vv = lk(jnp.zeros((cap,), jnp.int64))
                    else:
                        # last PEER is local — peers are whole per segment
                        val, vv = vals[peer_end], va[peer_end]
                    out_c[ci.id] = val
                    out_v[ci.id] = vv & sel_sorted
                    continue
                # running aggregates to the last peer (default RANGE
                # UNBOUNDED PRECEDING..CURRENT ROW): local prefix value
                # plus the prior segments' whole-segment totals
                lv = sel_sorted if valid is None else (sel_sorted & valid)
                if fname in ("sum", "count", "avg"):
                    if fname == "count" and vals is None:
                        vals = jnp.ones((cap,), dtype=jnp.int64)
                    acc = (jnp.float64 if vals.dtype.kind == "f"
                           else jnp.int64)
                    contrib = jnp.where(lv, vals.astype(acc), acc(0))
                    cs = jnp.cumsum(contrib)
                    cnt = jnp.cumsum(lv.astype(jnp.int64))
                    ps = jnp.sum(jnp.where(
                        prior_mask, lax.all_gather(
                            jnp.sum(contrib), SEG_AXIS), acc(0)))
                    pc = jnp.sum(jnp.where(
                        prior_mask, lax.all_gather(
                            jnp.sum(lv.astype(jnp.int64)), SEG_AXIS), 0))
                    s = cs[peer_end] + ps
                    c = cnt[peer_end] + pc
                    if fname == "count":
                        out_c[ci.id] = c
                        out_v.pop(ci.id, None)
                    elif fname == "sum":
                        out_c[ci.id] = s
                        out_v[ci.id] = c > 0
                    else:
                        a = (s.astype(jnp.float64)
                             / jnp.where(c == 0, 1, c).astype(jnp.float64))
                        if scale:
                            a = a / (10.0 ** scale)
                        out_c[ci.id] = a
                        out_v[ci.id] = c > 0
                    continue
                # min / max (identity-fill rule of ops/window.py)
                if vals.dtype.kind == "f":
                    ident = jnp.array(jnp.inf if fname == "min"
                                      else -jnp.inf, vals.dtype)
                else:
                    info = jnp.iinfo(vals.dtype)
                    ident = jnp.array(info.max if fname == "min"
                                      else info.min, vals.dtype)
                filled = jnp.where(lv, vals, ident)
                op = jnp.minimum if fname == "min" else jnp.maximum
                run_ = (lax.cummin(filled) if fname == "min"
                        else lax.cummax(filled))
                g_t = lax.all_gather(
                    jnp.min(filled) if fname == "min"
                    else jnp.max(filled), SEG_AXIS)
                prior_red = (jnp.min(jnp.where(prior_mask, g_t, ident))
                             if fname == "min"
                             else jnp.max(jnp.where(prior_mask, g_t,
                                                    ident)))
                cnt = jnp.cumsum(lv.astype(jnp.int64))
                pc = jnp.sum(jnp.where(
                    prior_mask, lax.all_gather(
                        jnp.sum(lv.astype(jnp.int64)), SEG_AXIS), 0))
                out_c[ci.id] = op(run_[peer_end], prior_red)
                out_v[ci.id] = (cnt[peer_end] + pc) > 0
            return Batch(out_c, out_v, sel_sorted)

        return run

    # ---- union ---------------------------------------------------------
    def _c_union(self, plan: Union):
        fns = [self._compile_node(c) for c in plan.inputs]
        branch_ids = plan.branch_ids
        loci = [c.locus for c in plan.inputs]

        def run(ctx):
            from jax import lax

            parts_c = {uc.id: [] for uc in plan.cols}
            parts_v = {uc.id: [] for uc in plan.cols}
            parts_sel = []
            for fn, ids, locus in zip(fns, branch_ids, loci):
                b = fn(ctx)
                sel = b.selection()
                if locus is not None and locus.kind in (
                        LocusKind.SEGMENT_GENERAL, LocusKind.GENERAL):
                    # replicated branch: keep one segment's copy
                    sel = sel & (lax.axis_index(SEG_AXIS) == 0)
                parts_sel.append(sel)
                for uc, bid in zip(plan.cols, ids):
                    parts_c[uc.id].append(b.cols[bid])
                    v = b.valids.get(bid)
                    parts_v[uc.id].append(
                        v if v is not None else jnp.ones_like(sel))
            cols = {k: jnp.concatenate(v) for k, v in parts_c.items()}
            valids = {k: jnp.concatenate(v) for k, v in parts_v.items()}
            sel = jnp.concatenate(parts_sel)
            return Batch(cols, valids, sel)

        return run

    # ---- sort / limit --------------------------------------------------
    def _sort_keys(self, batch: Batch, keys):
        ev = Evaluator(batch, self.consts)
        out = []
        for e, desc, nf in keys:
            v, valid = ev.value(e)
            lut = None
            if e.type.kind is T.Kind.TEXT:
                d = getattr(e, "_dict_ref", None)
                if d is None and isinstance(e, E.ColRef):
                    d = self._dict_for_col(e.name)
                if d is not None:
                    dic = self.store.dictionary(*d)
                    order = np.argsort(np.argsort(dic.values, kind="stable"), kind="stable")
                    lut = jnp.asarray(
                        np.concatenate([order.astype(np.int32), [np.int32(-1)]]))
            out.append(sort_ops.SortKey(v, valid, e.type, desc, nf, rank_lut=lut))
        return out

    def _c_sort(self, plan: Sort):
        child_fn = self._compile_node(plan.child)
        keys = plan.keys
        cap = self._capacity_of(plan.child)
        key_bounds = getattr(plan, "key_bounds", None)
        fid_pack = None
        # mirror order_pack_bits' static feasibility: registering a flag
        # that runtime packing can never use ships a permanently-zero flag
        # (plus a pmax collective in multihost) per execution (advisor r3)
        if (key_bounds is not None
                and self._nid(plan) not in self.pack_disabled
                and _static_order_packable(keys, key_bounds)):
            fid_pack = f"pack_overflow_{len(self.flags)}"
            self.flags.append(fid_pack)
            self.flag_packs[fid_pack] = self._nid(plan)
        else:
            key_bounds = None

        def run(ctx):
            b = child_fn(ctx)
            sk = self._sort_keys(b, keys)
            kb = key_bounds
            if kb is not None and sort_ops.order_pack_bits(sk, kb) is None:
                kb = None
            perm, sel_sorted, viol = sort_ops.sort_batch(
                sk, b.selection(), cap, kb)
            if fid_pack is not None:
                ctx["flags"].append(
                    (fid_pack, viol if viol is not None
                     else jnp.zeros((), bool)))
            cols, valids = sort_ops.apply_perm(b.cols, b.valids, perm)
            return Batch(cols, valids, sel_sorted)

        return run

    def _c_limit(self, plan: Limit):
        child_fn = self._compile_node(plan.child)
        cap = self._capacity_of(plan.child)
        # LIMIT 0 is a real limit ('or' would treat 0 as no-limit and
        # disagree with _capacity_of's 'is not None' — advisor finding r1)
        k = min(cap, (cap if plan.limit is None else plan.limit) + plan.offset)
        compacted = isinstance(plan.child, Sort)
        # a buried Limit (not the host-trimmed one below the gather) must
        # drop its OFFSET prefix itself: rows are compacted live-first, so
        # masking the first `offset` positions removes exactly those rows
        device_offset = plan.offset if id(plan) != self._host_limit_node else 0

        def run(ctx):
            b = child_fn(ctx)
            if compacted:
                cols, valids, sel = sort_ops.limit(
                    b.cols, b.valids, b.selection(), k)
            else:
                # unsorted LIMIT: gather-compact live rows (order-preserving,
                # no lax.sort) straight into the k-slot output
                cols, valids, sel = sort_ops.compact(
                    b.cols, b.valids, b.selection(), k)
            if device_offset:
                sel = sel & (jnp.arange(k, dtype=jnp.int32) >= device_offset)
            return Batch(cols, valids, sel)

        return run


def _collect_dict_refs(plan: Plan, out: dict):
    for c in plan.out_cols():
        if c.dict_ref is not None:
            out[c.id] = c.dict_ref
    for ch in plan.children:
        _collect_dict_refs(ch, out)


def _collect_value_dict_refs(v, out: dict):
    """Dictionary refs reachable from an arbitrary plan-node field value:
    expression trees carry them as ``_dict_ref`` attributes (hash LUTs,
    sort-rank LUTs bake that dictionary's CONTENT into the program),
    ColInfos as their ``dict_ref`` field. Feeds shape_signature."""
    if isinstance(v, E.Expr):
        for n in E.walk(v):
            d = getattr(n, "_dict_ref", None)
            if d is not None:
                out[("expr", id(n))] = tuple(d)
    elif isinstance(v, (tuple, list)):
        for x in v:
            _collect_value_dict_refs(x, out)
    elif getattr(v, "dict_ref", None) is not None:
        out[("ci", id(v))] = tuple(v.dict_ref)
