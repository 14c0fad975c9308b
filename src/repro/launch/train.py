"""Production training launcher — compressed bytes on disk → train loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 100 --reduced --batch 8 --seq 128 \
        --archive corpus.acegad --prefetch 2 --unroll 4

The data plane is the query plane: the corpus archive opens (or encodes
ONCE, then `--archive` persists it — later invocations start from the
compressed bytes on disk, no re-encode) into a `GenomicArchive`, and
`ga.dataset(...)` drives training — async prefetch decodes batch k+1
through DecodePlan/BlockCache while step k runs, `--unroll U` feeds
(U, B, T) windows (ONE DecodePlan per window) to a `lax.scan`-unrolled
donated train step. Process hygiene (tcmalloc LD_PRELOAD re-exec,
log-noise env) applies before the backend initializes, and the persistent
compile cache (`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`)
before the first compile.

Full-config multi-pod launches use the same path with the production
mesh; on this CPU container you run reduced configs (the full configs
are exercised by the dry-run, which is the point of ShapeDtypeStruct
lowering).
"""
import argparse
import os
import sys

from repro.launch import hygiene

# allocator swap + env must precede the first jax backend touch; the
# argparse pass happens later, so the re-exec trigger is a plain argv scan
hygiene.maybe_reexec_tcmalloc("--tcmalloc" in sys.argv)
hygiene.apply_process_hygiene()

import jax  # noqa: E402  (after hygiene, deliberately)

from repro.checkpoint.checkpointer import Checkpointer, CheckpointConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data.fastq import make_fastq  # noqa: E402
from repro.api.archive import GenomicArchive  # noqa: E402
from repro.distributed.fault_tolerance import run_resilient_training  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.training.optimizer import AdamWConfig  # noqa: E402
from repro.training.train_step import (init_train_state,  # noqa: E402
                                       make_manual_dp_step, make_train_step,
                                       make_unrolled_train_step)


def build_archive(args) -> GenomicArchive:
    """`--archive PATH` existing → open it (compressed bytes on disk →
    device; zero encode work). Otherwise encode the corpus once —
    through the autotuner when `--tune-target` is set, else with the
    declared block size — and, when `--archive` names a path, save the
    result there so the NEXT invocation opens instead of encoding."""
    rec = args.seq + 1
    if args.archive and os.path.exists(args.archive):
        ga = GenomicArchive.open(args.archive,
                                 cache_blocks=args.cache_blocks)
        got = ga.store.index.starts[1] - ga.store.index.starts[0] \
            if ga.store.index is not None else 0
        if int(got) != rec:
            raise SystemExit(
                f"--archive {args.archive} holds {int(got)}-byte records "
                f"but --seq {args.seq} needs {rec}; re-encode or fix --seq")
        print(f"opened archive {args.archive} ({ga.stats().n_blocks} "
              f"blocks, no re-encode)")
        return ga
    corpus = make_fastq("platinum", n_reads=args.reads, seed=0)
    if args.tune_target:
        ga = GenomicArchive.create(corpus, target=args.tune_target,
                                   record_bytes=rec,
                                   cache_blocks=args.cache_blocks)
        print(f"autotuned profile: {ga.profile.describe()}")
    else:
        ga = GenomicArchive.from_records(corpus, record_bytes=rec,
                                        block_size=args.block,
                                        cache_blocks=args.cache_blocks)
    if args.archive:
        n = ga.save(args.archive)
        print(f"saved archive -> {args.archive} ({n} B)")
    return ga


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots"])
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--manual-dp", action="store_true",
                    help="shard_map DP with explicit psum")
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8 gradient all-reduce (requires --manual-dp)")
    ap.add_argument("--resume", action="store_true")
    # ------------------------------------------------------- data plane
    ap.add_argument("--archive", default=None, metavar="PATH",
                    help="pre-built archive (GenomicArchive.save). "
                         "Exists: open it, skip encoding. Missing: encode "
                         "once, save here for next time.")
    ap.add_argument("--tune-target", default=None,
                    choices=["seek", "ratio", "throughput"],
                    help="autotune the encode profile (repro.tune) "
                         "instead of hardcoding --block")
    ap.add_argument("--block", type=int, default=16 * 1024)
    ap.add_argument("--reads", type=int, default=4000,
                    help="synthetic corpus size when encoding")
    ap.add_argument("--cache-blocks", type=int, default=0)
    ap.add_argument("--prefetch", type=int, default=2,
                    help="async prefetch queue depth (0 = synchronous)")
    ap.add_argument("--unroll", type=int, default=1,
                    help="lax.scan-unrolled steps per dispatch; the "
                         "window decodes through ONE DecodePlan")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tcmalloc", action="store_true",
                    help="re-exec with tcmalloc LD_PRELOADed")
    args = ap.parse_args()
    hygiene.enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                      total_steps=args.steps)

    ga = build_archive(args)
    ds = ga.dataset(batch_size=args.batch, seq_len=args.seq,
                    prefetch=args.prefetch, seed=args.seed)
    st = ga.stats()
    print(f"corpus {st.raw_size} B raw -> {st.compressed_device_bytes} B "
          f"device-resident ({st.raw_size / max(1, st.compressed_device_bytes):.2f}x); {ds!r}")

    state = init_train_state(model, jax.random.key(0), opt)
    start = 0
    ck = Checkpointer(CheckpointConfig(
        directory=os.path.join(args.ckpt_dir, args.arch)))
    if args.resume and ck.latest_step() is not None:
        restored = ck.restore()
        manifest = restored.pop("_manifest")
        state = restored
        start = int(manifest["extra"].get("step", 0))
        ds.load_state_dict(manifest["extra"]["loader"])
        print(f"resumed from step {start} (dataset step {ds.step})")

    unroll = max(1, args.unroll)
    if args.manual_dp:
        if unroll > 1:
            raise SystemExit("--unroll pairs with the jit step; "
                             "drop it for --manual-dp")
        mesh = make_local_mesh()
        inner = make_manual_dp_step(model, opt, mesh, remat=args.remat,
                                    compress=args.grad_compress)
        key = jax.random.key(1)

        def step(st, batch):
            return inner(st, batch, key)

        make_stream = None
    elif unroll > 1:
        step = make_unrolled_train_step(model, opt, remat=args.remat)
        make_stream = lambda: ds.windows(unroll)       # noqa: E731
    else:
        step = jax.jit(make_train_step(model, opt, remat=args.remat))
        make_stream = None

    run_resilient_training(step, state, None, ck, n_steps=args.steps,
                           start_step=start, ckpt_every=args.ckpt_every,
                           loader=ds, log_every=10,
                           steps_per_batch=unroll, make_stream=make_stream)
    print("training complete;", ck.latest_step())


if __name__ == "__main__":
    main()
