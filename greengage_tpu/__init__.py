"""greengage_tpu — a TPU-native MPP analytical query engine.

A brand-new framework with the capabilities of GreengageDB (Greenplum-lineage
PostgreSQL MPP data warehouse), redesigned TPU-first:

- segments -> chips of a ``jax.sharding.Mesh`` (axis "seg")
- slice/Motion execution -> whole-plan compilation under ``shard_map`` where
  Redistribute Motion = ``lax.all_to_all``, Broadcast Motion = ``all_gather``,
  Gather Motion = device->host gather (reference: src/backend/cdb/motion/)
- volcano tuple-at-a-time -> vectorized columnar batch operators with
  validity + selection masks (reference: src/backend/executor/)
- AOCS column store -> per-column compressed block files with checksums and
  manifest-based MVCC commit (reference: src/backend/access/aocs/aocsam.c)
- locus-based motion planning (reference: src/backend/cdb/cdbpathlocus.c,
  cdbpath.c:922 cdbpath_motion_for_join)

See SURVEY.md for the full structural map of the reference.
"""

import os

import jax

# Decimals are stored/computed as scaled int64 for SQL exactness (the
# reference relies on PostgreSQL numeric); int64 on TPU is emulated with
# int32 pairs which is acceptable for the bandwidth-bound analytical ops.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache, set HERE and nowhere else. Query
# programs compile per (plan shape, capacity tier), and a fresh process
# (CLI call, server restart, a chip run) must find them again on disk.
# The directory never moves with the platform, the pid or the clock:
# where JAX_COMPILATION_CACHE_DIR is set jax already reads it and this
# package neither redirects nor cleans it; otherwise <checkout>/.jax_cache.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))
# persist small programs too: the test suite and every fresh process
# recompile the same statement shapes, and sub-second compiles add up
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

__version__ = "0.1.0"

from greengage_tpu.api import Database, connect  # noqa: E402,F401
