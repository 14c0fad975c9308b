"""The program's trace names: device scopes in the compiled decode and
host spans on the read path (`repro.trace`)."""
import contextlib
import glob
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import trace
from repro.core import decoder as D
from repro.core import encoder as enc
from repro.data.fastq import make_fastq
from repro.resilience.parity import _xor_rebuild

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
# the module's source-location tables, which name the calling lines
_SOURCE_TABLE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ .*)$\n",
    re.MULTILINE)


def _program(hlo_text: str) -> str:
    """The compiled program without its metadata."""
    return _METADATA.sub("", _SOURCE_TABLE.sub("", hlo_text))


def _scopes_in(hlo_text: str) -> set:
    """Every scope of the table named as a path token of an op_name."""
    tokens = set()
    for path in _OP_NAME.findall(hlo_text):
        tokens.update(re.split(r"[/()]", path))
    return tokens & set(trace.SCOPES)


@pytest.fixture(scope="module")
def decoders():
    data = make_fastq("platinum", n_reads=600, seed=0)
    return {mode: D.Decoder(enc.encode(data, block_size=bs, mode=mode))
            for mode, bs in (("ra", 16384), ("global", 4096))}


def _compiled_decode(dec) -> str:
    n = min(8, dec.da.n_blocks)
    meta = (dec._meta(n, total=n * dec.da.block_size)
            if dec.da.mode == "global" else dec._meta(n))
    return D._decode_sel_jit.lower(
        dec.arrays, jnp.arange(n, dtype=jnp.int32), da_meta=meta,
        backend=dec.backend).compile().as_text()


@pytest.mark.parametrize("mode", ["ra", "global"])
def test_every_stage_scope_names_compiled_decode_ops(decoders, mode):
    assert _scopes_in(_compiled_decode(decoders[mode])) == set(
        trace.DECODE_STAGES)


@pytest.mark.parametrize("mode", ["ra", "global"])
def test_stage_scopes_change_only_the_metadata(decoders, mode,
                                               monkeypatch):
    """The same decode compiled without the scopes is the same program,
    instruction for instruction, once the metadata is left out."""
    scoped = _compiled_decode(decoders[mode])
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_decode(decoders[mode])
    jax.clear_caches()
    assert not _scopes_in(plain)
    assert _program(scoped) == _program(plain)


def test_verify_and_parity_scopes_name_their_ops():
    fnv = D._fnv_rows_jit.lower(
        jnp.zeros((4, 64), jnp.uint8), jnp.full((4,), 64, jnp.int32)
    ).compile().as_text()
    xor = _xor_rebuild.lower(
        jnp.zeros(256, jnp.uint16), jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.int32), jnp.zeros(16, jnp.uint16),
        jnp.int32(0), jnp.int32(16)).compile().as_text()
    assert _scopes_in(fnv) == {trace.VERIFY_FNV}
    assert _scopes_in(xor) == {trace.PARITY_XOR}


def test_host_span_names_are_prefixed_and_distinct():
    assert len(set(trace.HOST_SPANS)) == len(trace.HOST_SPANS)
    assert all(n.startswith(trace.PREFIX) for n in trace.HOST_SPANS)
    assert not set(trace.HOST_SPANS) & set(trace.SCOPES)


def test_read_path_records_its_host_spans(tmp_path):
    """A point read through the serving frontend and a streamed range
    record every host span of the table while a profiler runs."""
    from jax.profiler import ProfileData
    from repro.api import ByteRange, GenomicArchive
    from repro.api.executors import StreamingExecutor
    from repro.serving.frontend import ServingFrontend
    data = make_fastq("platinum", n_reads=600, seed=2)
    ga = GenomicArchive.from_bytes(data, block_size=4096, mode="ra",
                                   entropy="rans", cache_blocks=4)
    fe = ServingFrontend({"c": ga}, max_batch=4)
    fe.register_tenant("t", "c")
    ex = StreamingExecutor(ga.store, max_resident_bytes=4 * 4096 * 2,
                           planner=ga.planner)

    def work():
        for r in (1, 200, 400):
            fe.submit("t", r)
        fe.drain()
        fe.submit("t", 1)                    # all its blocks are cached
        fe.drain()
        return b"".join(c.tobytes()
                        for c in ex.chunks([ByteRange(0, 5 * 4096)]))

    assert work() == data[:5 * 4096]         # compiled outside the trace
    ga.clear_cache()                         # the traced reads miss
    jax.profiler.start_trace(str(tmp_path))
    assert work() == data[:5 * 4096]
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert set(trace.HOST_SPANS) <= names


def test_spans_cost_nothing_that_changes_results():
    """Host spans wrap calls without touching what they return."""
    @trace.spanned(trace.PLAN)
    def f(x, y=1):
        """doc"""
        return x + y

    assert f(2, y=3) == 5 and f.__name__ == "f" and f.__doc__ == "doc"
    with trace.span(trace.DECODE_LAUNCH, rows=8, rounds=None):
        got = np.arange(3).sum()
    assert got == 3
