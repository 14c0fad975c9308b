"""Names of the program's trace spans and device scopes, in one table.

Host spans mark where the read path spends host time: each is a
`jax.profiler.TraceAnnotation`, recorded only while a profiler runs (off,
one costs about a microsecond) and on the device trace's clock, so a
reduction can put each idle gap of the device down to the innermost span
around it. Every host span name starts with `PREFIX`.

Device scopes (`jax.named_scope`) name the stages of the jitted decode in
the HLO metadata (`op_name`); they change no computation. A stage's
device time is the time of the ops whose `op_name` path holds its scope.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "repro."

# host spans, outermost first
FRONTEND_STEP = PREFIX + "frontend.step"  # ServingFrontend.step, arg step
STREAM_CHUNK = PREFIX + "stream.chunk"    # StreamingExecutor._execute, chunk
PLAN = PREFIX + "plan"                    # QueryPlanner.plan*
CACHE_PLAN = PREFIX + "cache.plan"        # BlockCache.plan
DECODE_LAUNCH = PREFIX + "decode.launch"  # a _decode_sel_jit dispatch, args
                                          # rows and rounds
CACHE_INSTALL = PREFIX + "cache.install"  # the _install_gather dispatch
CACHE_GATHER = PREFIX + "cache.gather"    # the all-hit _gather_slots one
GATHER = PREFIX + "gather"                # a _gather_jit dispatch
TO_HOST = PREFIX + "to_host"              # the host waits for device bytes
STREAM_ASSEMBLE = PREFIX + "stream.assemble"  # crop and join a chunk
HOST_SPANS = (FRONTEND_STEP, STREAM_CHUNK, PLAN, CACHE_PLAN, DECODE_LAUNCH,
              CACHE_INSTALL, CACHE_GATHER, GATHER, TO_HOST, STREAM_ASSEMBLE)

# device scopes of the decode kernel's stages, in pipeline order
DECODE_RANS = "decode.rans"            # the rANS scan
DECODE_LINEARIZE = "decode.linearize"  # step-major rANS rows -> streams
DECODE_EXPAND = "decode.expand"        # command planes -> byte pointers
DECODE_RESOLVE = "decode.resolve"      # pointer doubling + literal payout
DECODE_STAGES = (DECODE_RANS, DECODE_LINEARIZE, DECODE_EXPAND,
                 DECODE_RESOLVE)
# device scopes outside the decode
VERIFY_FNV = "verify.fnv"              # per-row FNV-1a-64 digest scan
PARITY_XOR = "parity.xor"              # parity reconstruction XOR-gather
SCOPES = DECODE_STAGES + (VERIFY_FNV, PARITY_XOR)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span `name` with `args` as its trace arguments."""
    return jax.profiler.TraceAnnotation(name, **args)


def spanned(name: str):
    """Decorator: run the function inside a host span `name`."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
