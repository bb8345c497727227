"""The compressed store: each column encoded once with the program's public
encoder (``repro.core.plan.encode`` and ``TABLE2_PLANS``), cached on disk.

Encoding is ETL in ZipFlow: columns are compressed once and scanned many
times.  ``ensure`` encodes in worker processes that run with
``JAX_PLATFORMS=cpu``, one column each, before the benchmark's own process
touches a device, and keeps the blobs under ``<root>/<config>/<key>/``.  The
key folds in the configuration, the seed and a digest of the encoder's
sources, the Table-2 plans and the benchmark's generator, so a run whose
store is cached does not encode.

``attach`` is the one place that knows how ``ColumnPipeline`` registers
blobs.  Importing this module imports neither JAX nor the program.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import time
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
DEFAULT_ROOT = BENCH / ".store"
# the sources whose change changes the blobs
DIGEST_SOURCES = (ROOT / "src/repro/algos", ROOT / "src/repro/core/plan.py",
                  ROOT / "src/repro/data/columns.py", BENCH / "tpch/gen.py")
KEEP = 8        # stores kept per configuration; older ones are deleted


def source_digest(paths=DIGEST_SOURCES) -> str:
    h = hashlib.sha256()
    for p in paths:
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            h.update(str(f.relative_to(p) if p.is_dir() else p.name).encode())
            h.update(f.read_bytes())
    return h.hexdigest()[:16]


def store_key(config: dict, seed: int, digest: str) -> str:
    """What decides the blobs: the columns, their scale and the quantities
    the seed does not draw, the seed and the sources that make and encode
    them (the Table-2 plans among them)."""
    blob_cfg = {k: config.get(k) for k in ("columns", "block_scale",
                                           "blocks", "fixed_streams")}
    cfg = hashlib.sha256(json.dumps(blob_cfg, sort_keys=True).encode())
    return f"{int(seed)}-{cfg.hexdigest()[:12]}-{digest}"


def _worker_init() -> None:
    # before the worker imports JAX: it encodes on the host and must never
    # take the chip the benchmark's own process holds
    os.environ["JAX_PLATFORMS"] = "cpu"


def _encode_column(gen_args: tuple, name: str, path: str) -> dict:
    """Worker: make the column from the seed, encode it, write it to
    ``path``.  Reports which JAX backends the worker initialised."""
    from bench.tpch import gen
    from repro.core.plan import encode
    from repro.data.columns import TABLE2_PLANS

    t0 = time.perf_counter()
    arr = gen.generate([name], *gen_args)[name]
    enc = encode(TABLE2_PLANS[name], arr)
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        pickle.dump(enc, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    from jax._src import xla_bridge

    return {"column": name, "seconds": time.perf_counter() - t0,
            "backends": sorted(xla_bridge._backends)}


def ensure(config: dict, gen_args: tuple, seed: int, root: Path = DEFAULT_ROOT,
           log=print) -> tuple[Path, dict]:
    """The store directory of ``config`` at ``seed``, encoding what is
    missing.  Returns (directory, report) where report says whether it hit
    and what the workers did."""
    digest = source_digest()
    d = Path(root) / config["name"] / store_key(config, seed, digest)
    done = d / "done.json"
    if done.exists():
        os.utime(done)
        log(f"store: hit {d} (no encoding)")
        return d, {"hit": True, "workers": []}
    d.mkdir(parents=True, exist_ok=True)
    cols = list(config["columns"])
    log(f"store: miss {d}; encoding {len(cols)} columns in "
        f"{len(cols)} worker processes")
    t0 = time.perf_counter()
    ctx = get_context("spawn")
    with ProcessPoolExecutor(max_workers=len(cols), mp_context=ctx,
                             initializer=_worker_init) as pool:
        futs = [pool.submit(_encode_column, gen_args, c, str(d / f"{c}.pkl"))
                for c in cols]
        reports = [f.result() for f in futs]
    done.write_text(json.dumps({"columns": cols, "digest": digest,
                                "seconds": time.perf_counter() - t0}))
    for r in reports:
        log(f"store: encoded {r['column']} in {r['seconds']:.3f} s")
    _prune(Path(root) / config["name"], keep=d)
    return d, {"hit": False, "workers": reports}


def _prune(parent: Path, keep: Path) -> None:
    """Delete all but the KEEP most recently used stores of a configuration."""
    stores = sorted((p for p in parent.iterdir() if p.is_dir()),
                    key=lambda p: (p / "done.json").stat().st_mtime
                    if (p / "done.json").exists() else 0.0, reverse=True)
    for p in stores[KEEP:]:
        if p != keep:
            shutil.rmtree(p, ignore_errors=True)


def load(d: Path, columns) -> dict:
    """The blobs of ``columns`` from a store directory.  Only stores this
    benchmark wrote are read."""
    out = {}
    for c in columns:
        with open(d / f"{c}.pkl", "rb") as f:
            out[c] = pickle.load(f)
    return out


def attach(pipe, encoded: dict) -> None:
    """Register pre-encoded blobs with a ``ColumnPipeline`` exactly as
    ``ColumnPipeline.compress`` registers what it encodes (the pipeline has
    no public way yet: PERF.md, Open questions)."""
    for name, enc in encoded.items():
        pipe._encoded[name] = enc
        pipe._decoders[name] = pipe.executor.compile(name, enc)
    pipe._queries.clear()
    pipe._query_cfg.clear()
