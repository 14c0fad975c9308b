"""The stage split and the program's spans read from a trace
(`chipbench/stages.py`), and the decoder's counters that
`decode_pad_row_share.point` reads."""
import dataclasses
import time

import numpy as np
import pytest

from chipbench import harness, stages, trace as tracing, workcount

platinum_fastq = harness.load_module(harness.HERE, "corpora",
                                     "platinum_fastq").platinum_fastq


# ------------------------------------------------------------ synthetic
def test_gap_goes_to_the_innermost_span_on_the_path_of_most_overlap():
    spans = [("frontend.step", 0, 100), ("repro.frontend.step", 1, 99),
             ("repro.cache.plan", 10, 30), ("repro.to_host", 40, 90),
             ("generator.wait", 100, 200)]
    assert stages.attribute((12, 28), spans) == "repro.cache.plan"
    # most of this gap lies in the wait, past the step's last span
    assert stages.attribute((85, 160), spans) == "generator.wait"
    # inside the program's step but under none of its children
    assert stages.attribute((31, 39), spans) == "repro.frontend.step"
    # mostly the step's own time, though a child overlaps its start
    assert stages.attribute((25, 39), spans) == "repro.frontend.step"
    assert stages.attribute((300, 400), spans) == "no span"


def test_self_times_count_nested_events_once():
    ops = [("m", "decode.resolve", 0, 100),      # a while ...
           ("m", "decode.resolve", 10, 30),      # ... and its body
           ("m", "unscoped", 40, 50),            # an op of no scope inside
           ("m", "decode.rans", 120, 150)]
    assert stages.self_times(ops, 0, 200) == {
        ("m", "decode.resolve"): 90, ("m", "unscoped"): 10,
        ("m", "decode.rans"): 30}
    # clipped to the window
    assert stages.self_times(ops, 20, 130) == {
        ("m", "decode.resolve"): 70, ("m", "unscoped"): 10,
        ("m", "decode.rans"): 10}


def test_stage_of_reads_the_innermost_scope_of_a_path():
    assert stages.stage_of(
        "jit(_decode_sel_core)/vmap(decode.expand)/jit(remainder)") \
        == "decode.expand"
    assert stages.stage_of("jit(f)/decode.rans/while/body:") == "decode.rans"
    assert stages.stage_of("jit(_decode_sel_core)/gather:") \
        == stages.UNSCOPED


def test_host_ms_leaves_out_the_waits_for_the_device():
    spans = [("repro.frontend.step", 0, 10e6),
             ("repro.to_host", 2e6, 6e6),
             ("repro.frontend.step", 20e6, 23e6)]
    assert stages.host_ms(spans, "repro.frontend.step", "repro.to_host",
                          0, 30e6) == [6.0, 3.0]
    assert stages.spans_inside(spans, "repro.frontend.step", 0, 30e6) \
        == [2, 1]


# A device plane as the chip writes it: "XLA Modules" events named
# `<module>(<program id>)`, and on "XLA Ops" operations named by their HLO
# text, a `while` enclosing its body; each operation's event metadata
# carries its program id and its op_name (`tf_op`). Times in picoseconds.
_CHIP_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 60000000 }
    events { metadata_id: 4 offset_ps: 40000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 60000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__decode_sel_core(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = u8[8] fusion()"
    stats { metadata_id: 1 uint64_value: 7 }
    stats { metadata_id: 2 str_value: "RANS" } } }
  event_metadata { key: 3 value { id: 3 name: "%while.2 = s32[8] while()"
    stats { metadata_id: 1 uint64_value: 7 }
    stats { metadata_id: 2 str_value: "LOOP" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.3 = s32[8] fusion()"
    stats { metadata_id: 1 uint64_value: 7 }
    stats { metadata_id: 2 str_value: "BODY" } } }
  event_metadata { key: 5 value { id: 5 name: "%copy.4 = s32[8] copy()"
    stats { metadata_id: 1 uint64_value: 7 } } }
  stat_metadata { key: 1 value { id: 1 name: "program_id" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
}
"""


def test_chip_shaped_trace_splits_the_module_by_stage(tmp_path):
    from jax.profiler import ProfileData
    text = (_CHIP_TRACE
            .replace("RANS", "jit(_decode_sel_core)/decode.rans/while:")
            .replace("LOOP", "jit(_decode_sel_core)/decode.resolve/while:")
            .replace("BODY",
                     "jit(_decode_sel_core)/vmap(decode.resolve)/gather:"))
    path = tmp_path / "chip.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    got = stages.reduce(str(path), decoded_bytes=75_000)
    us = {k: round(v * 1e6, 6) for k, v in got["stage_s"].items()}
    # the while counts its 60 us less the 5 us copy of no scope inside
    # it (its body, 10 us, is the same stage); the module's idle 10 us,
    # none
    assert us == {"decode.rans": 20, "decode.resolve": 55,
                  stages.UNSCOPED: 5}
    assert sum(got["stage_s"].values()) <= got["module_s"]
    assert got["module_s"] == pytest.approx(1e-4)
    assert got["busy_s"] == pytest.approx(80e-6)
    assert got["entropy_stage_GBps"] == pytest.approx(75e3 / 20e-6 / 1e9)
    assert got["match_stage_GBps"] == pytest.approx(75e3 / 55e-6 / 1e9)


# ------------------------------------------------------------ recorded
@pytest.fixture(scope="module")
def nested_trace(tmp_path_factory):
    """Benchmark spans around program spans, one of them idling the
    device on the host."""
    import jax
    import jax.numpy as jnp
    from repro import trace as program
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    d = tmp_path_factory.mktemp("nested")
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        for step in range(2):
            with jax.profiler.TraceAnnotation("frontend.step"):
                with program.span(program.FRONTEND_STEP, step=step):
                    with program.span(program.CACHE_PLAN):
                        time.sleep(0.04)
                    y = f(x)
                    with program.span(program.TO_HOST):
                        np.asarray(y)
        with jax.profiler.TraceAnnotation("generator.wait"):
            time.sleep(0.08)
    jax.profiler.stop_trace()
    return tracing.latest_xplane(str(d))


def test_recorded_gaps_take_the_innermost_program_span(nested_trace):
    got = stages.reduce(nested_trace)
    names = [n for n, s in got["idle_gaps"] if s >= 0.03]
    assert names == ["generator.wait", "repro.cache.plan",
                     "repro.cache.plan"]
    assert got["steps"] == 2 and 40 <= got["host_ms_per_step"] < 1000
    assert got["spans_per.repro.frontend.step"] == 3


def test_program_spans_leave_the_benchmarks_reduction_as_it_was(
        nested_trace):
    """The benchmark's own reduction reads the same busy time, idle share
    and executable seconds with the program's spans in the trace and
    without them, and names the same gaps by its own spans."""
    events = tracing.load(nested_trace)
    with_spans = tracing.reduce(events)
    stripped = tracing.reduce(dataclasses.replace(
        events, spans=[sp for sp in events.spans
                       if sp[0] in tracing.HOST_SPANS]))
    assert not set(stages.PROGRAM_SPANS) & set(tracing.HOST_SPANS)
    assert stripped == with_spans
    got = stages.reduce(nested_trace)
    assert got["busy_s"] == with_spans.busy_s
    assert got["idle_share"] == with_spans.idle_share
    assert [s for _, s in got["idle_gaps"]] == [
        s for _, s in with_spans.idle_gaps]


@pytest.fixture(scope="module")
def ga16k():
    """16 KiB blocks of one depth bucket, as the point cell's."""
    from repro.api import GenomicArchive
    data = platinum_fastq(3000, 100, seed=5)
    ga = GenomicArchive.from_bytes(data, block_size=16384, mode="ra",
                                   entropy="rans", cache_blocks=16)
    assert not ga.store.decoder.multi_bucket
    return ga


@pytest.fixture(scope="module")
def ga4k():
    """4 KiB blocks of several depth buckets."""
    from repro.api import GenomicArchive
    data = platinum_fastq(3000, 100, seed=5)
    ga = GenomicArchive.from_bytes(data, block_size=4096, mode="ra",
                                   entropy="rans", cache_blocks=16)
    assert ga.store.decoder.multi_bucket
    return ga


def test_recorded_decode_splits_into_every_stage(ga16k, tmp_path):
    """A CPU trace of point reads, with the HLO protos that name its
    operations: every decode stage has time, and the stages sum to the
    decode's operations."""
    import jax
    ids = np.arange(0, ga16k.n_reads, 97)
    ga16k.clear_cache()
    ga16k.store.fetch_reads(ids)                  # compiled outside
    ga16k.clear_cache()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        ga16k.store.fetch_reads(ids)[0].block_until_ready()
    jax.profiler.stop_trace()
    got = stages.reduce(tracing.latest_xplane(str(tmp_path)))
    assert set(stages.STAGES[:4]) <= set(got["stage_s"])
    assert all(v > 0 for v in got["stage_s"].values())
    assert got["stage_s"][stages.UNSCOPED] < sum(got["stage_s"].values())


# ------------------------------------------------------------ counters
@pytest.mark.parametrize("archive", ["ga4k", "ga16k"])
def test_decode_info_counts_the_blocks_the_log_records(archive, request):
    """Each launch's distinct blocks, summed, are the log's; an archive
    of several depth buckets launches once per bucket of a call."""
    ga = request.getfixturevalue(archive)
    dec = ga.store.decoder
    log = workcount.DecodeLog([dec])
    try:
        ga.clear_cache()
        before, mark = dec.decode_info(), log.mark()
        rng = np.random.default_rng(4)
        for _ in range(6):
            ga.store.fetch_reads(rng.integers(ga.n_reads, size=9))
        after = dec.decode_info()
        (got,) = log.since(mark)
    finally:
        del dec.decode_blocks            # back to the class's method
    delta = {k: after[k] - before[k] for k in after}
    calls = log.mark() - mark
    assert calls > 0
    assert (delta["launches"] > calls if dec.multi_bucket
            else delta["launches"] == calls)
    assert delta["blocks"] == got.size
    assert delta["rows"] == delta["blocks"] + delta["pad_rows"]
    assert {f"decoder_{k}": v for k, v in after.items()}.items() \
        <= ga.cache_info().items()


def test_five_misses_count_three_pad_rows(ga16k):
    """The cache pads a miss batch of 5 blocks to 8 rows."""
    ga = ga16k
    starts = ga.store._starts64
    bs = ga.block_size
    first, last = starts[:-1] // bs, (starts[1:] - 1) // bs
    one_block = np.flatnonzero(first == last)
    # one read inside each of 5 distinct blocks
    _, pick = np.unique(first[one_block], return_index=True)
    ids = one_block[pick[:5]]
    ga.clear_cache()
    before = ga.cache_info()
    ga.store.fetch_reads(ids)
    after = ga.cache_info()
    delta = {k: after[f"decoder_{k}"] - before[f"decoder_{k}"]
             for k in ("launches", "rows", "blocks", "pad_rows")}
    assert delta == {"launches": 1, "rows": 8, "blocks": 5, "pad_rows": 3}
    assert after["decode_launches"] - before["decode_launches"] == 1
