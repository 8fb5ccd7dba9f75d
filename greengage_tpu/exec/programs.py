"""The compiled-program cache: find a statement's program or compile it.

Step one of the executor's attempt (docs/ARCHITECTURE.md), written once
for the classic loop (`Executor.run`) and batched serving
(`batchserve.prepare_batch`): the signature memo, the program LRU, the
capacity hints and the `compile` span live here and nowhere else. Knows
nothing of the executor's loop; `exec/compile.py` does the compiling.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

from greengage_tpu.exec.compile import Compiler, CompileResult, _pow2
from greengage_tpu.runtime import lockdebug
from greengage_tpu.runtime import trace as _trace
from greengage_tpu.runtime.logger import counters


class Unsignable(Exception):
    """The statement's shape could not be signed (e.g. an evicted transient
    raw dict), so its program can be neither found nor kept. The caller
    chooses: compile uncached (``cache_key`` None) or serve it elsewhere."""


def _estimates(plan) -> tuple:
    """The plan's row estimates, node by node: what the compiler reads of
    them (capacities, compactions) the rest of the memo key leaves out."""
    out, stack = [], [plan]
    while stack:
        p = stack.pop()
        out.append((p.est_rows, getattr(p, "expand_est", None)))
        stack.extend(p.children)
    return tuple(out)


class ProgramCache:
    def __init__(self, store, mesh, nseg: int, settings, multihost: bool):
        self.store = store   # and its catalog: Database.refresh rebinds it
        self.mesh = mesh
        self.nseg = nseg
        self.settings = settings
        self.multihost = multihost
        # _cache_mu guards ALL the bookkeeping below: the batch-serving
        # stager mutates it concurrently with statement threads (gg check
        # races). RLock: _insert -> _on_program_evicted nests. Critical
        # sections are dict ops only — never a compile, never device work.
        self._cache_mu = lockdebug.named(threading.RLock(),
                                         "executor._cache_mu")
        # compiled programs (the gang-reuse analog), REAL LRU:
        # (statement signature, shape signature, batch width bucket) ->
        # CompileResult. The shape signature (Compiler.shape_signature)
        # captures everything the trace reads — bucketed capacities,
        # dictionary fingerprints, consts digest, param dtypes — so a
        # manifest-version bump that stays inside every capacity bucket
        # and grows no dictionary REUSES the hot XLA executable instead
        # of recompiling. Bounded by the plan_cache_size GUC.
        self._plan_cache: OrderedDict = lockdebug.shared(
            OrderedDict(), "executor._plan_cache")
        # runtime cardinality feedback: the exact counts the device
        # reported for overflow-capable nodes (join expansion totals, agg
        # group counts, gather live rows) of a statement that paid an
        # overflow retry, so its NEXT compile (post-DML replan) sizes
        # those capacities right. cache_key -> {plan node id: capacity},
        # LRU (recency = last record OR last use) under a fixed backstop
        # bound; the primary lifetime tie is _on_program_evicted
        self._cap_hints: OrderedDict = lockdebug.shared(
            OrderedDict(), "executor._cap_hints")
        # memoized shape signatures; insertion-order bounded — entries
        # for dead manifest versions age out
        self._sig_memo: OrderedDict = OrderedDict()

    def find_or_compile(self, cache_key, plan, consts, snapshot, tier,
                        cap_overrides, batch_width=0, no_direct=False,
                        uncached=None) -> tuple[CompileResult, bool, float]:
        """-> (program, was it cached, compile ms). ``cache_key`` None, or
        any true value among ``uncached`` (the Compiler arguments of one
        run alone: instrument, scan_cap_override, aux_tables,
        pack_disabled), compiles without looking or keeping. A shape that
        cannot be signed raises Unsignable."""
        uncached = uncached or {}

        def compiler():
            # the batched program is compiled as on one host even in a
            # gang, as it always was (ROADMAP D7)
            return Compiler(self.store.catalog, self.store, self.mesh,
                            self.nseg, consts, self.settings, tier=tier,
                            cap_overrides=cap_overrides,
                            multihost=self.multihost and not batch_width,
                            no_direct=no_direct, batch_width=batch_width,
                            **uncached)

        ck = walker = None
        if cache_key is not None and not any(uncached.values()):
            # the digest is a pure function of these inputs (seg counts
            # and dictionary growth always bump the manifest version; the
            # bound plan is version-keyed in the session cache, and a
            # re-plan under a feedback promotion moves the estimates the
            # capacities and compactions follow), so a steady-state hit
            # skips the whole-plan signature walk
            mk = (cache_key, snapshot.get("version", 0), tier,
                  tuple(sorted(cap_overrides.items())), no_direct,
                  Compiler.codegen_settings_sig(self.settings),
                  _estimates(plan)) \
                + (("batch",) if batch_width else ())
            try:
                sig, walker = self._memo_signature(mk, compiler, plan,
                                                   snapshot)
            except Exception:
                # counted so a signature bug shows up as a visible reuse
                # regression, not silence
                counters.inc("program_cache_unsignable")
                raise Unsignable() from None
            # trailing 0 = the unbatched program; batched serving keys
            # its width buckets in the same LRU
            ck = (cache_key, sig, batch_width)
        # fetch + recency bump in one section: a concurrent statement's
        # eviction cannot interleave (the value stays alive once fetched)
        with self._cache_mu:
            comp = self._plan_cache.get(ck) if ck is not None else None
            if comp is not None:
                self._plan_cache.move_to_end(ck)
        if comp is not None:
            counters.inc("program_cache_hit")
            return comp, True, 0.0
        if ck is not None:
            counters.inc("program_cache_miss")
        t0 = time.monotonic()
        with _trace.span("compile", cached=False,
                         **({"batch_width": batch_width} if batch_width
                            else {"tier": tier})):
            # the signature walk's Compiler is reused where there is one
            # (same arguments by construction: nothing `uncached` is set)
            comp = (walker or compiler()).compile(plan)
        compile_ms = (time.monotonic() - t0) * 1e3
        if ck is not None:
            self._insert(ck, comp)
        return comp, False, compile_ms

    def _memo_signature(self, mk, make_compiler, plan, snapshot):
        """Memoized shape-signature walk -> (sig, walker Compiler or None
        when the memo hit). An unsignable shape raises through. The walker
        is returned so the compile on a miss can reuse its scan
        collection instead of re-walking."""
        with self._cache_mu:
            sig = self._sig_memo.get(mk)
        if sig is not None:
            return sig, None
        comp = make_compiler()
        # the walk runs unlocked (it reads plan/manifest state, not the
        # memo); only the memo insert is serialized
        sig = comp.shape_signature(plan, snapshot)
        with self._cache_mu:
            self._sig_memo[mk] = sig
            while len(self._sig_memo) > 2048:
                self._sig_memo.popitem(last=False)
        return sig, comp

    def _insert(self, ck, comp) -> None:
        """LRU-bounded (each entry pins an XLA executable); an eviction
        drops its statement's hints with the last of its programs."""
        with self._cache_mu:
            self._plan_cache[ck] = comp
            limit_n = max(int(getattr(self.settings,
                                      "plan_cache_size", 128)), 1)
            while len(self._plan_cache) > limit_n:
                old_k, _old = self._plan_cache.popitem(last=False)
                self._on_program_evicted(old_k)

    def _on_program_evicted(self, key) -> None:
        """When the LAST program of a statement leaves the LRU, its hints
        go too: their lifetime is tied to the plan cache."""
        cache_key = key[0]
        with self._cache_mu:
            if any(k[0] == cache_key for k in list(self._plan_cache)):
                return
            self._cap_hints.pop(cache_key, None)

    def invalidate_table(self, table: str) -> None:
        """Drop compiled programs scanning ``table`` (DROP TABLE / DROP
        PARTITION): a same-named recreated table could otherwise alias a
        stale executable whose shape signature coincides."""
        base = table.split("#", 1)[0]
        with self._cache_mu:
            stale = [k for k, c in list(self._plan_cache.items())
                     if any(t == table or t.split("#", 1)[0] == base
                            for t, *_ in c.input_spec)]
            for k in stale:
                self._plan_cache.pop(k, None)
            for k in stale:
                self._on_program_evicted(k)

    def items(self) -> list:
        """[(key, CompileResult)] as of now (`gg mem`'s executables)."""
        with self._cache_mu:
            return list(self._plan_cache.items())

    # ---- capacity hints: "this node needed capacity N" ---------------
    # planner/feedback.py persists the same fact; merging the two stores
    # is ROADMAP D7 (it changes which program largevol_power_1chip runs).
    def hints(self, cache_key, feedback=None) -> dict:
        """{plan node id: capacity} a compile of this statement starts
        from: this process's, else ``feedback``'s persisted ones, so a
        restarted process sizes overflow-capable capacities right on its
        FIRST dispatch instead of through overflow retries."""
        with self._cache_mu:
            hints = dict(self._cap_hints.get(cache_key) or {})
            if hints:
                self._cap_hints.move_to_end(cache_key)
        if not hints and cache_key is not None and feedback is not None:
            hints = dict(feedback.caps(cache_key))
        return hints

    def record_hints(self, cache_key, needs: dict, feedback=None) -> None:
        """``needs``: {plan node id: exact count the device reported}."""
        with self._cache_mu:
            rec = self._cap_hints.setdefault(cache_key, {})
            self._cap_hints.move_to_end(cache_key)
            for nid, need in needs.items():
                # pow2 bucket: small data drift re-records the SAME hint,
                # so hint-sized programs keep their executable-cache
                # entry across DML
                rec[nid] = _pow2(need + max(need // 16, 64))
            while len(self._cap_hints) > 512:
                self._cap_hints.popitem(last=False)
            rec = dict(rec)
        if feedback is not None:
            # mirrored so a restarted process inherits the sizing
            feedback.note_caps(cache_key, rec)
