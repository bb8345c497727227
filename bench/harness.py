"""The benchmark harness, driven by ``BENCHMARK.json`` and the files it names.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<name>.json``: columns, scale,
pipeline settings, limits) and a traffic mix (``bench/traffic/<name>.json``,
read by ``UNITS``).  Each metric is a reader in ``bench/metrics/<name>.py``
and each query a module in ``bench/tpch/queries/<name>.py``; all are found by
the names in ``BENCHMARK.json``.

Order of a run: the compressed store (worker processes make the columns
from the seed and encode them; cached, and the encoding is not counted in
``setup_s``) -> the device -> ``ColumnPipeline``
with the stored blobs -> warm-up until a whole unit of traffic compiles
nothing -> the measured window of closed-loop units -> peak device memory
-> the program's state freed -> the source columns made again from the
seed -> the comparison that decides ``correct``.  The last
lines of standard error give each compared number beside its limit; the
last line of standard output is the result as JSON.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import store
from bench.tpch import gen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
WARM_CAP = 6            # warm-up units at most, while each one compiles


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ lookups

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str, base: Path = BENCH) -> Path:
    """The file of a traffic mix, metric or query by name (a configuration's
    file is named in ``BENCHMARK.json``)."""
    where = {"traffic": ("traffic", ".json"), "metric": ("metrics", ".py"),
             "query": ("tpch/queries", ".py")}
    sub, ext = where[kind]
    path = base / sub / f"{name}{ext}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} {name!r}: {path} is missing")
    return path


def load_module(path: Path):
    mod_name = "_bench_" + re.sub(r"\W", "_", str(path.with_suffix("")))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: dict        # name -> BENCHMARK.json entry, for this run's kind


def load_cell(bench_json: Path, workload: str, traced: bool,
              base: Path = BENCH) -> Cell:
    spec = load_json(bench_json)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_json}")
    w = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = load_json(bench_json.parent / cfg_entry["file"])
    traffic = load_json(find("traffic", w["traffic"], base))
    kind = "per_layer" if traced else "end_to_end"
    metrics = {m["name"]: m for m in spec[kind]
               if workload in m.get("workloads", [workload])}
    return Cell(workload, int(w["chips"]), config, traffic, metrics)


# ------------------------------------------------------------------ a run

@dataclass
class Run:
    """What the metric readers read."""

    setup_s: float = 0.0
    window_s: float = 0.0
    plain_bytes: int = 0            # plain bytes the completed units read
    needed_bytes: int = 0           # compressed read + plain outputs written
    scans: int = 0                  # queries or streams completed
    launches: int = 0               # decode launches of those scans
    transfer_s: list = field(default_factory=list)  # per fused query
    window_compiles: int = 0
    peak_bytes: int | None = None
    trace: dict | None = None       # bench.trace.reduce output
    peaks: dict | None = None       # bench/peaks.json entry of the device


class Compiles:
    """Backend compile events seen since the listener was registered."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_s: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1


def distinct_launches(records) -> int:
    """Decode launches of ColumnExec records; batched members share one."""
    seen, n = set(), 0
    for name, rec in records.items():
        grp = frozenset((name,) + tuple(rec.batched_with))
        if grp not in seen:
            seen.add(grp)
            n += rec.decode_launches
    return n


class QueryRound:
    """One closed-loop client sending the traffic's queries in turn, fused
    into decode (``ColumnPipeline.run_query``).  A unit is one round."""

    def __init__(self, pipe, traffic, encoded):
        self.pipe = pipe
        self.queries = {q: load_module(find("query", q))
                        for q in traffic["queries"]}
        self.scans_per_unit = len(self.queries)
        self.plans = {q: m.plan() for q, m in self.queries.items()}
        self.plain = {q: sum(encoded[c].plain_nbytes for c in m.COLUMNS)
                      for q, m in self.queries.items()}
        self.compressed = {q: sum(encoded[c].compressed_nbytes
                                  for c in m.COLUMNS)
                           for q, m in self.queries.items()}
        self.results: dict[str, list] = {q: [] for q in self.queries}

    def __call__(self, run: Run | None):
        import jax

        with jax.profiler.TraceAnnotation("bench.round"):
            for q, qplan in self.plans.items():
                with jax.profiler.TraceAnnotation(f"bench.query.{q}"):
                    qe = self.pipe.run_query(qplan)
                    with jax.profiler.TraceAnnotation("bench.finalize"):
                        result = np.asarray(qe.result)
                if run is None:
                    continue
                self.results[q].append(result)
                run.transfer_s.append(qe.transfer_s)
                run.scans += 1
                run.launches += qe.decode_launches + distinct_launches(
                    qe.resident)
                run.plain_bytes += self.plain[q]
                run.needed_bytes += self.compressed[q]

    def checks(self, cols, limits) -> dict:
        out = {}
        for q, mod in self.queries.items():
            want = mod.reference({c: cols[c] for c in mod.COLUMNS})
            errs = [mod.rel_error(mod.read(r), want) for r in self.results[q]]
            out[f"{q}_rel_err"] = (max(errs) if errs else float("inf"),
                                   float(limits[f"{q}_rel_err"]))
        return out


class Stream:
    """One closed-loop client materializing every column of the
    configuration into device memory (``ColumnPipeline.run``).  A unit is
    one stream; its outputs are released before the next, and the last
    one's are kept for the comparison."""

    def __init__(self, pipe, traffic, encoded):
        self.pipe = pipe
        self.plain = sum(e.plain_nbytes for e in encoded.values())
        self.compressed = sum(e.compressed_nbytes for e in encoded.values())
        self.scans_per_unit = 1
        self.last = None

    def __call__(self, run: Run | None):
        import jax

        self.last = None                    # release the previous outputs
        with jax.profiler.TraceAnnotation("bench.stream"):
            res = self.pipe.run()
            with jax.profiler.TraceAnnotation("bench.finalize"):
                jax.block_until_ready([r.array for r in res.values()])
        self.last = {n: r.array for n, r in res.items()}
        if run is not None:
            run.scans += 1
            run.launches += distinct_launches(res)
            run.plain_bytes += self.plain
            run.needed_bytes += self.compressed + self.plain

    def fetch(self) -> None:
        """The kept outputs to the host, so device state can be freed."""
        if self.last is not None:
            self.last = {n: np.asarray(a) for n, a in self.last.items()}

    def checks(self, cols, limits) -> dict:
        bad = 0
        if self.last is None or set(self.last) != set(cols):
            bad = sum(a.size for a in cols.values())
        else:
            for n, want in cols.items():
                got = np.asarray(self.last[n])
                if got.shape != want.shape or got.dtype != want.dtype:
                    bad += want.size
                    continue
                bits = f"u{want.itemsize}"
                bad += int(np.count_nonzero(got.view(bits) != want.view(bits)))
        return {"mismatched_values": (bad, float(limits["mismatched_values"]))}


UNITS = {"query_round": QueryRound, "stream": Stream}


# ------------------------------------------------------------------ main

def parse(argv):
    ap = argparse.ArgumentParser(description="ZipFlow chip benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any platform at the configuration's "
                         "rehearsal scale, without the compile cache")
    ap.add_argument("--store-dir", type=Path, default=None,
                    help="where the compressed store lives "
                         "(default bench/.store)")
    return ap.parse_args(argv)


def device_info(devices, run: Run) -> dict:
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": run.peak_bytes}
    if run.trace is not None:
        info["busy_s"] = run.trace["busy_s"]
        info["window_s"] = run.trace["window_s"]
    return info


def gen_args(cfg: dict, seed: int, rehearse: bool) -> tuple:
    """``bench.tpch.gen.generate``'s arguments after the column names."""
    scale = cfg["rehearsal"] if rehearse else cfg
    return (float(scale["block_scale"]), int(scale["blocks"]), seed,
            tuple(cfg.get("fixed_streams", ())))


def host_data(cfg: dict, seed: int, rehearse: bool, store_root: Path):
    """The configuration's blobs from the store, encoded in worker processes
    on a miss, and the seconds that encoding took (0 on a hit).  Touches no
    device."""
    t = time.perf_counter()
    args = gen_args(cfg, seed, rehearse)
    store_cfg = {**cfg, "block_scale": args[0], "blocks": args[1]}
    sdir, srep = store.ensure(store_cfg, args, seed, store_root, log)
    encode_s = 0.0 if srep["hit"] else time.perf_counter() - t
    encoded = store.load(sdir, cfg["columns"])
    log(f"store: {'hit' if srep['hit'] else 'miss'}, "
        f"{sum(e.plain_nbytes for e in encoded.values()) / 1e9:.6f} GB plain, "
        f"{sum(e.compressed_nbytes for e in encoded.values()) / 1e9:.6f} GB "
        f"compressed, encoding {encode_s:.3f} s (not set-up), "
        f"{time.perf_counter() - t:.3f} s")
    return encoded, encode_s


def source_columns(cfg: dict, seed: int, rehearse: bool) -> dict:
    """The columns the store was encoded from, made again from the seed for
    the comparison."""
    t = time.perf_counter()
    cols = gen.generate(cfg["columns"], *gen_args(cfg, seed, rehearse))
    log(f"data: {cfg['name']} seed {seed}: "
        f"{ {c: a.size for c, a in cols.items()} } rows, "
        f"{time.perf_counter() - t:.3f} s")
    return cols


def open_devices(chips: int, rehearse: bool):
    """JAX's devices and the peaks of their kind; None where the platform
    is not a TPU (outside a rehearsal) or the chips are too few."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}")
    if dev.platform != "tpu" and not rehearse:
        log(f"no TPU: JAX reports {dev.platform!r}; --rehearse runs elsewhere")
        return None
    if len(devices) < chips:
        log(f"needs {chips} chips, JAX has {len(devices)}")
        return None
    if rehearse:
        return devices, None
    table = load_json(BENCH / "peaks.json")
    if dev.device_kind not in table:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} "
                       "in bench/peaks.json")
    cache = BENCH / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache}")
    return devices, table[dev.device_kind]


def build_unit(cell: Cell, encoded, hooks=None):
    """A ``ColumnPipeline`` with the stored blobs, driven by the traffic's
    unit.  ``hooks(pipe)`` (tests only) may break the pipeline."""
    from repro.data.columns import TABLE2_PLANS
    from repro.data.loader import ColumnPipeline

    cfg = cell.config
    pipe = ColumnPipeline({c: TABLE2_PLANS[c] for c in cfg["columns"]},
                          **cfg["pipeline"])
    store.attach(pipe, encoded)
    if hooks is not None:
        hooks(pipe)
    return UNITS[cell.traffic["unit"]](pipe, cell.traffic, encoded)


def warm_up(unit, compiles: Compiles) -> None:
    """Units until one compiles nothing, at most WARM_CAP."""
    for i in range(WARM_CAP):
        c0, t = compiles.count, time.perf_counter()
        unit(None)
        log(f"warm-up {i}: {time.perf_counter() - t:.3f} s, "
            f"{compiles.count - c0} compiles")
        if compiles.count == c0:
            break


def window(unit, run: Run, seconds: float, compiles: Compiles,
           t0: float) -> tuple[int, int, str | None]:
    """Closed-loop units until one completes ``seconds`` after the first
    issue.  Returns (attempted, failed, error)."""
    attempted = failed = 0
    error = None
    c0 = compiles.count
    t_start = time.perf_counter()
    run.setup_s = t_start - t0
    t_end = t_start
    while True:
        attempted += unit.scans_per_unit
        c1 = compiles.count
        try:
            unit(run)
        except Exception:               # the unit failed: report, stop
            failed += unit.scans_per_unit
            error = traceback.format_exc()
            log(error)
            break
        t = time.perf_counter()
        log(f"unit: {t - t_end:.6f} s, {compiles.count - c1} compiles")
        t_end = t
        if t_end - t_start >= seconds:
            break
    run.window_s = t_end - t_start
    run.window_compiles = compiles.count - c0
    return attempted, failed, error


def release(unit) -> None:
    """Free the program's device state; a stream's kept outputs go to the
    host first."""
    if isinstance(unit, Stream):
        unit.fetch()
    unit.pipe = None
    gc.collect()


def main(argv=None, t0: float | None = None, hooks=None) -> int:
    """Run one cell; returns the exit code."""
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    cell = load_cell(ROOT / "BENCHMARK.json", args.workload, bool(args.trace))
    cfg = cell.config
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(",") and not args.rehearse:
        # known before any data is made: JAX is held off the TPU
        log(f"no TPU: JAX_PLATFORMS={platforms!r}; --rehearse runs elsewhere")
        return 2
    store_root = args.store_dir or store.DEFAULT_ROOT
    encoded, encode_s = host_data(cfg, args.seed, args.rehearse, store_root)
    # encoding is ETL, paid once per seed and checkout, not by each run:
    # set-up starts again where it ends, so a store miss and a hit read alike
    t0 += encode_s
    opened = open_devices(cell.chips, args.rehearse)
    if opened is None:
        return 2
    devices, peaks = opened
    import jax

    compiles = Compiles()
    unit = build_unit(cell, encoded, hooks)
    run = Run(peaks=peaks)
    warm_up(unit, compiles)

    trace_dir = (store_root.parent if args.store_dir else BENCH) / ".traces" \
        / f"{cell.name}-{args.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.trace:
        jax.profiler.start_trace(str(trace_dir))
    attempted, failed, error = window(unit, run, args.seconds, compiles, t0)
    if args.trace:
        jax.profiler.stop_trace()
    stats = devices[0].memory_stats()
    run.peak_bytes = None if stats is None else stats.get("peak_bytes_in_use")
    log(f"window: {run.window_s:.3f} s, {run.scans} scans, "
        f"{run.window_compiles} compiles, peak {run.peak_bytes}")
    if args.trace:
        from bench import trace as trace_mod

        path = trace_mod.find_xplane(str(trace_dir))
        run.trace = trace_mod.reduce_file(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for name, entry in cell.metrics.items():
        value = load_module(find("metric", name)).read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}

    # the program's state goes before the reference runs
    release(unit)
    del encoded
    checks = {}
    if error is None:
        t = time.perf_counter()
        cols = source_columns(cfg, args.seed, args.rehearse)
        checks = unit.checks(cols, cfg["limits"])
        log(f"reference: {time.perf_counter() - t:.3f} s")
    correct = (error is None and failed == 0 and run.scans > 0 and
               all(v <= lim for v, lim in checks.values()))
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAILED'}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device_info(devices, run)}
    if run.trace is not None:
        result["breakdown"] = run.trace["breakdown"]
    # an infinite gap (a Q1 group missing) prints as a string: JSON has
    # no infinity
    result["checks"] = {n: {"value": v if math.isfinite(v) else str(v),
                            "limit": lim}
                        for n, (v, lim) in checks.items()}
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0
