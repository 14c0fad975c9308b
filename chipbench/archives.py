"""Encoded archives, cached per (configuration, seed, program source).

A deployment opens an archive that already exists, so a run encodes only
the first time a (configuration, seed) pair meets a checkout: it encodes
through the corpus module's `build`, writes the result with
`GenomicArchive.save` under `chipbench/.archives/`, and later runs
`GenomicArchive.open` it. The file's key hashes the configuration's
file, the seed and every file under the program's `src/`, so an archive
that an older program wrote is never read.
"""
from __future__ import annotations

import hashlib
import json
from pathlib import Path
from typing import Callable

HERE = Path(__file__).resolve().parent
ARCHIVE_DIR = HERE / ".archives"


def source_digest(src: Path) -> str:
    """sha256 over the relative path and bytes of every file under `src`
    (byte-compiled caches aside)."""
    h = hashlib.sha256()
    for p in sorted(src.rglob("*")):
        if not p.is_file() or "__pycache__" in p.parts or p.suffix == ".pyc":
            continue
        h.update(str(p.relative_to(src)).encode() + b"\0")
        h.update(p.read_bytes() + b"\0")
    return h.hexdigest()


def archive_key(config_name: str, config: dict, seed: int, src: Path) -> str:
    h = hashlib.sha256()
    h.update(json.dumps([config_name, config, int(seed)],
                        sort_keys=True).encode())
    h.update(source_digest(src).encode())
    return h.hexdigest()[:24]


def open_or_build(config_name: str, config: dict, seed: int, corpus: bytes,
                  build: Callable, src: Path, directory: Path = ARCHIVE_DIR):
    """(GenomicArchive, built) for this configuration and seed: opened
    from the cache, or built by `build(corpus, config, **cache)` and then
    saved there."""
    from repro.api import GenomicArchive
    path = directory / (f"{config_name}-{int(seed)}-"
                        f"{archive_key(config_name, config, seed, src)}"
                        f".acegad")
    cache = dict(cache_blocks=int(config.get("cache_blocks", 0)),
                 cache_policy=config.get("cache_policy", "lru"))
    if path.exists():
        return GenomicArchive.open(str(path), **cache), False
    ga = build(corpus, config, **cache)
    directory.mkdir(parents=True, exist_ok=True)
    ga.save(str(path))
    return ga, True
