"""One-time JAX runtime configuration for the compute path.

Deliberately does NOT enable global x64: TPUs have no native int64 —
under ``jax_enable_x64`` every count/reduce lowers to emulated 64-bit
arithmetic, measured ~1000x slower than int32 on the popcount matrix
path.  The engine's contract instead is:

- device accumulations are int32, which is always exact per
  (shard, row): one shard holds 2^20 columns, so any per-shard popcount
  fits comfortably (2^20 << 2^31);
- cross-shard totals that could exceed int32 (>2047 full shards ≈ 2.1B
  columns) are finished on the HOST in int64/python ints — see
  ``engine.kernels.shard_totals`` and the host combine helpers in
  ``engine.bsi``.
"""

import os
import warnings

import jax

# Persistent compilation cache: ONE rule, decided here before the first
# compile.  An operator (or the machine image) places the cache with
# JAX_COMPILATION_CACHE_DIR, which jax reads itself — then no code sets
# a directory.  Otherwise it lives at a FIXED path under the checkout:
# the path is part of what a warm start needs to find again, so never a
# temp name, pid or time.  Every server child imports
# this module, so all of them share one cache.  The two
# thresholds drop to "persist everything": the serving program set is
# small and every entry saves a first-query compile on the next boot.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def compile_cache_dir() -> str:
    """Where compiled programs persist (for the boot log)."""
    return jax.config.jax_compilation_cache_dir


# Donated ping-pong buffer chains (r17): the chain families pass a
# retired output buffer as a donated scratch argument so consecutive
# dispatches reuse its device memory instead of allocating fresh
# output each window.  The CPU backend (the tier-1 test platform)
# ignores the donation and warns per dispatch; the fallback is
# correct, so the warning is noise there — but ONLY there: on TPU a
# donation that cannot alias is a silent perf regression, so the
# warning must stay audible.  Env-gated (not jax.default_backend())
# to avoid initializing backends at import time.
if os.environ.get("JAX_PLATFORMS", "").strip().lower().startswith("cpu"):
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")
