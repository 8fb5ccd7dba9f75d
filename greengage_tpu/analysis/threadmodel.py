"""The declared thread topology of the engine — who runs on what thread.

The host side of this engine is genuinely multi-threaded: statement /
connection threads, the PR-3 staging pool, the PR-11 batch-serving
pipeline pair, the FTS prober, the multihost heartbeat and rejoin
acceptors, the spill prefetcher, the gpfdist loader. Every one of them
mutates shared structures (program/plan LRUs, the BlockCache registry,
counters, manifest state). The reference relies on decades of
battle-testing for this class of bug; we substitute a *declared model*
that two analyzers cross-check against the code:

* ``THREAD_ROLES`` names every thread role, the package call sites that
  spawn it, and the functions that are its entry points. The
  registry-hygiene check (``run`` below, check id ``threads``) walks the
  package for ``threading.Thread(target=...)`` / ``ThreadPoolExecutor``
  / ``ThreadingMixIn`` spawn sites and fails in BOTH directions: an
  unregistered spawn site (a new thread nobody modelled) and a declared
  spawn with no site (a stale model).
* ``lint_races.py`` (check id ``races``) walks interprocedurally from
  each role's entries and reports shared attributes written by one role
  and touched by another with no common lock.
* ``runtime/lockdebug.py``'s access witness maps live threads back to
  roles through ``ROLE_NAME_PREFIXES`` (every spawn site names its
  thread, so the name prefix IS the role tag at runtime).

The model is deliberately explicit rather than inferred: adding a
thread means adding a row here, which is exactly the moment to decide
what state it may touch and under which lock.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from greengage_tpu.analysis import astutil
from greengage_tpu.analysis.report import Report


@dataclass(frozen=True)
class Role:
    name: str
    description: str
    # ((path suffix, marker), ...): marker is the Thread target's trailing
    # name, "ThreadPoolExecutor" for pool construction, or "class:<Name>"
    # for a ThreadingMixIn-derived server class (its handler threads)
    spawns: tuple
    # ((path suffix, owning class or "", function name), ...) — the role's
    # entry points, where the race walk starts
    entries: tuple


THREAD_ROLES: dict[str, Role] = {
    "statement": Role(
        "statement",
        "statement/connection threads: Database.sql on the caller's "
        "thread, including every server handler thread executing it "
        "(and the inline staging pool at scan_threads=1). Handler.handle "
        "is an entry so the serving plane's shared state (SqlServer "
        "admission/drain bookkeeping, the per-connection watcher "
        "arm/disarm surface) is race-analyzed from the threads that "
        "actually touch it",
        spawns=(),          # spawned by callers/socketserver, not by us
        entries=(("exec/session.py", "Database", "sql"),
                 # server handler threads: admission, serve loop, drain
                 ("runtime/server.py", "Handler", "handle"),
                 # scan_threads=1 runs read units on the calling thread
                 ("exec/staging.py", "Stager", "_read_unit")),
    ),
    "server": Role(
        "server",
        "socket accept loops plus the per-CONNECTION client-disconnect "
        "watcher (_ConnWatcher: armed per statement, parked between "
        "statements; the handler threads themselves run statements and "
        "are modelled as the statement role)",
        spawns=(("runtime/server.py", "serve_forever"),
                ("runtime/server.py", "_loop"),
                ("runtime/server.py", "class:Server"),
                ("runtime/server.py", "class:TcpServer")),
        entries=(("runtime/server.py", "_ConnWatcher", "_loop"),),
    ),
    "staging": Role(
        "staging",
        "PR-3 staging pool workers: concurrent (table, segment, column) "
        "read+decode units through the store's caches",
        spawns=(("exec/staging.py", "ThreadPoolExecutor"),),
        entries=(("exec/staging.py", "Stager", "_read_unit"),),
    ),
    "spill-prefetch": Role(
        "spill-prefetch",
        "spill-pass read-ahead: warms pass k+1's block reads while pass "
        "k runs on device",
        spawns=(("exec/staging.py", "_warm"),),
        entries=(("exec/staging.py", "PassPrefetcher", "_warm"),),
    ),
    "motion-stage": Role(
        "motion-stage",
        "bucket-pipeline stager (exec/motionpipe.py): runs bucket k+1's "
        "side-effect-free stage callable (subset builds, workfile "
        "promotion reads) while the statement thread computes bucket k; "
        "slot handoff under the pipeline's own condition lock",
        spawns=(("exec/motionpipe.py", "_stage_loop"),),
        entries=(("exec/motionpipe.py", "BucketPipeline", "_stage_loop"),),
    ),
    "batch-stage": Role(
        "batch-stage",
        "vectorized-serving stager: pops admission windows and runs "
        "compile-or-reuse + admission + host staging",
        spawns=(("exec/batchserve.py", "_stage_loop"),),
        entries=(("exec/batchserve.py", "BatchServer", "_stage_loop"),),
    ),
    "batch-dispatch": Role(
        "batch-dispatch",
        "vectorized-serving dispatcher: device dispatch + per-member "
        "demux of staged batches",
        spawns=(("exec/batchserve.py", "_dispatch_loop"),),
        entries=(("exec/batchserve.py", "BatchServer", "_dispatch_loop"),),
    ),
    "fts": Role(
        "fts",
        "fault-tolerance prober daemon: segment health probes, mirror "
        "promotion, topology-version bumps",
        spawns=(("runtime/fts.py", "loop"),),
        entries=(("runtime/fts.py", "", "loop"),),
    ),
    "standby-watch": Role(
        "standby-watch",
        "coordinator-failover watcher daemon (runtime/standby.py "
        "StandbyWatcher): pulls the primary's commit tail into the "
        "standby, tracks the liveness beat, and fences + promotes when "
        "the primary is silent past standby_promote_deadline_s",
        spawns=(("runtime/standby.py", "loop"),),
        entries=(("runtime/standby.py", "StandbyWatcher", "loop"),),
    ),
    "heartbeat": Role(
        "heartbeat",
        "multihost idle ping/pong heartbeat over the coordinator "
        "channel",
        spawns=(("parallel/multihost.py", "loop"),),
        entries=(("parallel/multihost.py", "", "loop"),),
    ),
    "rejoin": Role(
        "rejoin",
        "multihost rejoin acceptor: collects re-dialing workers while a "
        "degraded gang serves",
        spawns=(("parallel/multihost.py", "accept_loop"),),
        entries=(("parallel/multihost.py", "", "accept_loop"),),
    ),
    "ingest": Role(
        "ingest",
        "gpfdist loader: HTTP chunk server handler threads plus the "
        "parallel chunk fetchers, and the streaming-plane deadline "
        "flusher (time-watermark micro-batch commits, idle reaping)",
        spawns=(("runtime/ingest.py", "serve_forever"),
                ("runtime/ingest.py", "one"),
                ("runtime/ingest.py", "class:Server"),
                ("runtime/ingest.py", "_flush_loop")),
        entries=(("runtime/ingest.py", "", "one"),
                 ("runtime/ingest.py", "", "do_GET"),
                 ("runtime/ingest.py", "StreamIngestor", "_flush_loop")),
    ),
}


# thread-name prefix -> role, first match wins; every spawn site above
# names its thread, so the runtime witness can tag accesses by role.
# Unmatched threads (MainThread, socketserver "Thread-N" handlers, test
# threads) default to "statement" — they run statements or behave as
# callers.
ROLE_NAME_PREFIXES: tuple = (
    ("gg-stage", "staging"),              # ThreadPoolExecutor prefix
    ("gg-spill-prefetch", "spill-prefetch"),
    ("gg-motion-stage", "motion-stage"),
    ("gg-batch-stage", "batch-stage"),
    ("gg-batch-dispatch", "batch-dispatch"),
    ("gg-client-watch", "server"),
    ("gg-server", "server"),
    ("gg-gpfdist", "ingest"),
    ("gg-ingest-flush", "ingest"),
    ("fts-prober", "fts"),
    ("gg-standby-watch", "standby-watch"),
    ("mh-heartbeat", "heartbeat"),
    ("mh-rejoin-accept", "rejoin"),
)

DEFAULT_ROLE = "statement"


def role_of_thread_name(name: str) -> str:
    for prefix, role in ROLE_NAME_PREFIXES:
        if name.startswith(prefix):
            return role
    return DEFAULT_ROLE


# Classes whose instances are genuinely SHARED across threads — the race
# analyzer only pairs accesses on these (and on module globals): a
# per-statement object (Compiler, Binder, Batch, Result, ...) has one
# static identity but a fresh instance per call, so pairing its
# attributes across roles would fabricate races. Adding a class here
# puts its whole attribute surface under cross-role analysis.
SHARED_CLASSES: dict[str, str] = {
    "Executor":          "one per Database; statement + serving pipeline",
    "ProgramCache":      "the executor's program LRU, signature memo and "
                         "capacity hints",
    "Stager":            "the executor's staging: stage cache + "
                         "dynamic-prune memo",
    "BatchServer":       "admission windows + pipeline queue",
    "CacheRegistry":     "global block-cache byte budget",
    "BlockCache":        "named member caches of the registry",
    "TableStore":        "storage read paths + self-heal state",
    "Manifest":          "compose memo + delta cache + commit log",
    "Counters":          "process-wide metric registry",
    "Histograms":        "process-wide metric registry",
    "ClusterLog":        "shared CSV appender",
    "Database":          "session state reached from handler threads",
    "StatementRegistry": "interrupt contexts, cancelled cross-thread",
    "StatementContext":  "flag set by watcher/FTS/runaway threads",
    "SqlServer":         "connection admission/drain state, mutated by "
                         "every handler thread and stop()",
    "_ConnWatcher":      "armed/epoch state shared between the handler "
                         "thread and its watcher",
    "OverloadController": "process-wide brownout state machine, "
                          "evaluated from any statement thread",
    "FTSProber":         "probe bookkeeping",
    "StreamIngestor":    "stream registry shared by server handler "
                         "threads and the deadline flusher",
    "StreamSession":     "per-stream buffer/watermarks, fed by handlers "
                         "and flushed by the deadline thread",
    "SegmentConfig":     "topology mutated by FTS, read at dispatch",
    "PassPrefetcher":    "kicked by the spill loop, joined at close",
    "BucketPipeline":    "slot exchange between the statement thread and "
                         "its motion stager, under the pipeline's "
                         "condition lock",
    "_OrderTable":       "lockdebug's own global table",
    "FeedbackStore":     "calibration scales read at plan time by every "
                         "statement thread, written by reconcile after "
                         "execution and by the serve loop's adopt()",
}

# Attribute name -> class name: receiver typing the race walk cannot
# infer from constructor assignments (factory returns). Lets generic
# method calls (`self.stage_cache.get(...)`) resolve into the shared
# class's methods instead of going dark.
RECEIVER_TYPES: dict[str, str] = {
    "stage_cache": "BlockCache",
    "blockcache": "CacheRegistry",
    # TableStore's named member caches (storage/table_store.py __init__,
    # all created by CacheRegistry.cache())
    "_block_cache": "BlockCache",
    "_footer_cache": "BlockCache",
    "_raw_cache": "BlockCache",
    "_hp_cache": "BlockCache",
    "_rawcode_cache": "BlockCache",
    "_rawprefix_cache": "BlockCache",
    # Database.feedback / Executor.feedback (planner/feedback.py store)
    "feedback": "FeedbackStore",
}


# ---------------------------------------------------------------------
# registry hygiene: every spawn site modelled, every model row live
# ---------------------------------------------------------------------

def _spawn_sites(src):
    """Yield (marker, lineno) for every thread-creating site in a module:
    Thread targets (trailing name), pool construction, ThreadingMixIn
    server classes."""
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call):
            name = astutil.call_name(node)
            if name == "Thread":
                target = None
                for kw in node.keywords:
                    if kw.arg == "target":
                        target = kw.value
                if target is None and node.args:
                    target = node.args[0]
                if target is None:
                    yield "Thread-without-target", node.lineno
                    continue
                if isinstance(target, ast.Attribute):
                    yield target.attr, node.lineno
                elif isinstance(target, ast.Name):
                    yield target.id, node.lineno
                else:
                    yield "Thread-computed-target", node.lineno
            elif name == "ThreadPoolExecutor":
                yield "ThreadPoolExecutor", node.lineno
        elif isinstance(node, ast.ClassDef):
            for base in node.bases:
                dn = astutil.dotted(base) or ""
                if "Threading" in dn:
                    yield f"class:{node.name}", node.lineno
                    break


def _declared() -> dict[tuple, list[str]]:
    """(path suffix, marker) -> [role names declaring it]."""
    out: dict[tuple, list[str]] = {}
    for role in THREAD_ROLES.values():
        for suffix, marker in role.spawns:
            out.setdefault((suffix, marker), []).append(role.name)
    return out


def run(sources=None) -> Report:
    """Check id ``threads``: cross-check spawn sites against THREAD_ROLES
    both ways, and that every declared entry point resolves to a real
    function."""
    report = Report()
    sources = sources if sources is not None else astutil.SourceSet(
        exclude=("greengage_tpu/analysis/",))
    declared = _declared()
    found: set[tuple] = set()
    nsites = 0
    for src in sources:
        for marker, lineno in _spawn_sites(src):
            nsites += 1
            hits = [(suffix, m) for (suffix, m) in declared
                    if m == marker and src.rel.endswith(suffix)]
            if not hits:
                if src.pragma_ok(lineno, "threads"):
                    continue
                report.add(
                    "threads", src.rel, lineno,
                    f"unregistered-spawn:{marker}",
                    f"thread spawn site (target {marker!r}) is not "
                    "declared in analysis/threadmodel.py THREAD_ROLES — "
                    "model the new thread role (and what state it may "
                    "touch) before shipping it")
            else:
                found.update(hits)
    for (suffix, marker), roles in sorted(declared.items()):
        if (suffix, marker) not in found:
            report.add(
                "threads", "analysis/threadmodel.py", 1,
                f"stale-spawn:{marker}",
                f"THREAD_ROLES role(s) {', '.join(roles)} declare spawn "
                f"({suffix!r}, {marker!r}) but no such site exists — "
                "stale model row")
    # entry points must resolve to real functions
    index: set[tuple] = set()
    for src in sources:
        for cls, fn in _function_index(src.tree):
            index.add((src.rel, cls, fn))
            index.add((src.rel, "", fn))
    for role in THREAD_ROLES.values():
        for suffix, cls, fn in role.entries:
            if not any(rel.endswith(suffix) and c == cls and f == fn
                       for rel, c, f in index):
                report.add(
                    "threads", "analysis/threadmodel.py", 1,
                    f"dead-entry:{role.name}:{fn}",
                    f"role {role.name!r} entry point ({suffix}, "
                    f"{cls or '<module>'}, {fn}) resolves to no function "
                    "in the package")
    report.notes["thread_spawn_sites"] = nsites
    report.notes["thread_roles"] = len(THREAD_ROLES)
    return report


def _function_index(tree: ast.Module):
    """Yield (owning class or '', function name) for every function,
    attributing nested defs to their nearest enclosing class (a thread
    body defined inside a method still runs with that class's self)."""
    def walk(node, cls):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                yield from walk(child, child.name)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield cls, child.name
                yield from walk(child, cls)
            else:
                yield from walk(child, cls)
    yield from walk(tree, "")
