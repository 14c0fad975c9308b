"""Process hygiene for launches (the olmax `run.sh` idiom, in-process).

Production JAX launchers front-load environment setup before the first
backend touch:

  * allocator — tcmalloc via LD_PRELOAD (needs a re-exec: the loader
    reads LD_PRELOAD before Python runs) + a large-alloc report
    threshold so multi-GB numpy buffers don't spam warnings;
  * log noise — TF_CPP_MIN_LOG_LEVEL=4 silences the libtpu/TF chatter
    that interleaves with step logs;
  * compile cache — `enable_compile_cache()` turns on JAX's persistent
    compilation cache, so a second run of the same shapes loads its
    executables instead of compiling them.

No XLA flags are set here: jaxlib parses XLA_FLAGS for its CPU client on
every host, a TPU host included, and exits at start-up on a flag that
client does not know, such as the TPU-only `--xla_step_marker_location`.

Everything is idempotent and respectful of the caller's environment: a
variable the user already set is never overwritten.
`apply_process_hygiene()` must run before the first jax backend touch
(import is fine; device use is not).
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Dict, Optional

# env defaults applied only when unset (user environment wins)
_ENV_DEFAULTS = {
    # numpy/jax host buffers of multi-GB corpora are expected, not a leak
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    # keep libtpu/TF runtime chatter out of the step logs
    "TF_CPP_MIN_LOG_LEVEL": "4",
}

# the checkout's own cache directory (git-ignored); a fixed path, because
# a cache that moves between runs is never hit again
_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
)

# sentinel so a re-exec'd child doesn't re-exec forever
_REEXEC_GUARD = "REPRO_TCMALLOC_REEXECED"


def find_tcmalloc() -> Optional[str]:
    for p in _TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def maybe_reexec_tcmalloc(enable: bool) -> bool:
    """Re-exec the current process with tcmalloc LD_PRELOADed (the only
    way to swap the allocator: the dynamic loader consumed LD_PRELOAD
    before Python started). No-op (False) when disabled, already
    preloaded, already re-exec'd, or the library isn't installed. Call
    FIRST — before jax or any large allocation."""
    if not enable or os.environ.get(_REEXEC_GUARD):
        return False
    lib = find_tcmalloc()
    if lib is None or "tcmalloc" in os.environ.get("LD_PRELOAD", ""):
        return False
    env = dict(os.environ)
    env["LD_PRELOAD"] = (lib + (" " + env["LD_PRELOAD"]
                                if env.get("LD_PRELOAD") else ""))
    env[_REEXEC_GUARD] = "1"
    env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                   _ENV_DEFAULTS["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"])
    os.execve(sys.executable, [sys.executable] + sys.argv, env)
    return True        # unreachable; keeps the signature honest


def apply_process_hygiene() -> Dict[str, str]:
    """Set the env defaults. Returns the variables actually changed
    (empty when the environment already had everything)."""
    changed: Dict[str, str] = {}
    for k, v in _ENV_DEFAULTS.items():
        if k not in os.environ:
            os.environ[k] = v
            changed[k] = v
    return changed


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Call before the first compile.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the directory (jax reads
    the variable itself, so no other is set here); otherwise the cache
    lives in `<checkout>/.jax_cache/`. Every executable is cached, however
    fast it compiled: the decode path is many small programs."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
