"""Smoke run of the main path on a TPU, through the entry points a user calls.

TPC-H lineitem columns (Q1 and Q6) at SF 10, made from ``--seed`` -> Table-2
compression on the host -> ``ColumnPipeline`` planned streaming H2D
(``StreamingExecutor``) -> on-device decode -> Q1/Q6 answers, materialized and
decode-fused -> ``ServePlanner`` serving concurrent requests.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --chips 4        # only run_sharded over four chips,
                                          # compared with a one-chip run()
    python chip_smoke.py --cpu-rehearsal  # small scale, any platform

Every check raises, so any failure exits non-zero.  Each phase prints its wall
seconds, the backend compiles it caused and the device's
``peak_bytes_in_use`` so far.  The last line of standard output is one JSON
object naming the device JAX reports.  Without ``--cpu-rehearsal`` a platform
other than ``tpu`` is an error; the script never chooses a platform itself.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.costmodel import LinkTopology  # noqa: E402
from repro.data.columns import TABLE2_PLANS  # noqa: E402
from repro.data.loader import ColumnPipeline  # noqa: E402
from repro.data.queries import (ANSWERS, ENGINES,  # noqa: E402
                                NUMPY_REFERENCES, QUERY_PLANS)
from repro.data.tpch import QUERY_COLUMNS, generate, scale_columns  # noqa: E402

COLUMNS = QUERY_COLUMNS[1] + [c for c in QUERY_COLUMNS[6]
                              if c not in QUERY_COLUMNS[1]]
RTOL = 1e-4
N_REQUESTS = 8
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Phases:
    """Per-phase wall seconds, backend compile count and device peak bytes."""

    def __init__(self, device):
        self.device = device
        self.compiles = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_s: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1

    def peak_bytes(self):
        stats = self.device.memory_stats()
        return None if stats is None else stats.get("peak_bytes_in_use")

    @contextlib.contextmanager
    def __call__(self, name: str):
        c0, t0 = self.compiles, time.perf_counter()
        yield
        print(f"phase {name}: {time.perf_counter() - t0:.3f} s, "
              f"compiles {self.compiles - c0}, "
              f"peak_bytes_in_use {self.peak_bytes()}", flush=True)


def check_equal(label: str, pairs: dict) -> None:
    """Raise, naming every column in ``pairs`` ({name: (decoded, source)})
    whose decoded array is not bit for bit its source."""
    bad = []
    for name, (got, want) in pairs.items():
        got = np.asarray(got)
        if got.shape != want.shape or got.dtype != want.dtype:
            bad.append(f"{name}: {got.dtype}{got.shape} != "
                       f"{want.dtype}{want.shape}")
            continue
        bits = f"u{got.itemsize}"           # compare bits, not values
        diff = np.flatnonzero(got.view(bits) != want.view(bits))
        if diff.size:
            i = int(diff[0])
            bad.append(f"{name}: {diff.size} of {got.size} differ, first at "
                       f"{i}: {got[i]!r} != {want[i]!r}")
    if bad:
        raise AssertionError(f"{label}: " + "; ".join(bad))


def check_close(name: str, got, want) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=RTOL, err_msg=name)
    nz = want != 0
    err = float(np.max(np.abs(got[nz] - want[nz]) / np.abs(want[nz]))) \
        if nz.any() else 0.0
    print(f"  {name}: max relative error {err:.3e} (bound {RTOL})", flush=True)


def make_columns(args, phase) -> dict[str, np.ndarray]:
    base, tiles = (0.02, 2) if args.cpu_rehearsal else (1.0, 10)
    with phase("data.generate"):
        cols = generate(scale=base, seed=args.seed)
        cols = scale_columns({n: cols[n] for n in COLUMNS}, tiles)
    n = len(cols[COLUMNS[0]])
    print(f"  TPC-H SF {base * tiles:g}: {n:,} lineitem rows, "
          f"{sum(a.nbytes for a in cols.values()) / 1e9:.3f} GB plain "
          f"over {len(cols)} columns", flush=True)
    return cols


def new_pipeline(cols, phase) -> ColumnPipeline:
    pipe = ColumnPipeline({n: TABLE2_PLANS[n] for n in cols}, policy="adaptive",
                          chunk_bytes="auto", chunk_decode=True)
    with phase("data.encode"):
        ratios = pipe.compress(cols)
    comp = sum(pipe.executor.column_profile(n).compressed_nbytes for n in cols)
    print(f"  compressed {comp / 1e9:.3f} GB; ratios "
          + ", ".join(f"{n} {r:.2f}x" for n, r in ratios.items()), flush=True)
    return pipe


def stream_and_queries(cols, phase) -> None:
    pipe = new_pipeline(cols, phase)
    for label, run in (("cold", pipe.run), ("warm", pipe.run),
                       ("async", lambda: pipe.executor.run(async_dispatch=True))):
        with phase(f"stream.{label}"):
            res = run()
            jax.block_until_ready([r.array for r in res.values()])
        check_equal(f"stream.{label}",
                    {n: (r.array, cols[n]) for n, r in res.items()})
        launches = sum(r.decode_launches for r in res.values())
        print(f"  stream.{label}: {len(res)} columns bit-exact, "
              f"{launches} decode launches, "
              f"{sum(r.chunk_decoded for r in res.values())} chunk-decoded",
              flush=True)
    device_cols = {n: r.array for n, r in res.items()}
    del res
    with phase("reference.numpy"):
        refs = {q: NUMPY_REFERENCES[q]({c: cols[c] for c in QUERY_COLUMNS[q]})
                for q in (1, 6)}
    for q in (1, 6):
        engine = jax.jit(ENGINES[q])
        qcols = {c: device_cols[c] for c in QUERY_COLUMNS[q]}
        for label in ("cold", "warm"):
            with phase(f"query.materialized.q{q}.{label}"):
                out = jax.block_until_ready(engine(qcols))
            check_close(f"materialized Q{q} {label}", ANSWERS[q](out), refs[q])
    del device_cols
    for q in (1, 6):
        for label in ("cold", "warm"):
            with phase(f"query.fused.q{q}.{label}"):
                qe = pipe.run_query(QUERY_PLANS[q])
                result = ANSWERS[q](qe.result)
            check_close(f"fused Q{q} {label} ({qe.n_chunks} chunks)", result,
                        refs[q])
    serve(cols, pipe, phase)


def serve(cols, pipe, phase) -> None:
    """N_REQUESTS concurrent clients, each asking for the Q1 (even) or Q6
    (odd) column set of its own row partition of the table."""
    n = len(cols[COLUMNS[0]])
    bounds = np.linspace(0, n, N_REQUESTS + 1).astype(int)

    def request_columns(i: int):
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        part = {c: cols[c][lo:hi] for c in QUERY_COLUMNS[1 if i % 2 == 0 else 6]}
        return part, pipe.encode_request(part)

    with phase("serve.encode"):
        # each client compresses its own request, all at once
        with ThreadPoolExecutor(N_REQUESTS) as pool:
            wants = list(pool.map(request_columns, range(N_REQUESTS)))
    sp = pipe.serve_planner()
    reqs: list = [None] * N_REQUESTS
    gate = threading.Barrier(N_REQUESTS)

    def client(i: int) -> None:
        gate.wait()
        reqs[i] = sp.submit(f"r{i}", wants[i][1])

    with phase("serve.requests"):
        sp.start()
        try:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(N_REQUESTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
                if t.is_alive():
                    raise TimeoutError("a client thread did not finish")
            for r in reqs:
                if not r.wait(timeout=1200):
                    raise TimeoutError(f"request {r.rid} was not served")
        finally:
            sp.stop()
    for r, (part, _) in zip(reqs, wants):
        if r.error is not None:
            raise RuntimeError(f"request {r.rid} failed") from r.error
        check_equal(f"serve {r.rid}",
                    {c: (r.arrays[c], arr) for c, arr in part.items()})
    lat = sorted(r.latency_s for r in reqs)
    print(f"  serve: {len(reqs)}/{N_REQUESTS} requests served bit-exact, no "
          f"errors, {len(sp.reports)} waves, latency min {lat[0]:.3f} s "
          f"max {lat[-1]:.3f} s", flush=True)


def sharded(cols, phase, n_chips: int) -> None:
    """run_sharded over ``n_chips`` devices, default placement and
    placement="sharded", each compared with a one-chip run()."""
    pipe = new_pipeline(cols, phase)
    with phase("mesh.one_chip_run"):
        ref = {n: np.asarray(r.array) for n, r in pipe.run().items()}
    check_equal("one-chip", {n: (ref[n], cols[n]) for n in cols})
    spec = pipe.executor.cost_model.spec
    # the chip-to-chip fabric priced from the chip table, relative to the
    # host link, so the placement run may land shards anywhere and move them
    fabric = LinkTopology(n_links=n_chips,
                          d2d_scale=spec.host_link_gbps / spec.ici_gbps_per_link)
    for placement in (None, "sharded"):
        # threshold 0: every group-chunkable column with enough groups may
        # split into one shard per chip
        plan = pipe.mesh_plan(
            n_chips, placement=placement, shard_threshold_bytes=0,
            topology=fabric if placement else None)
        label = f"mesh.placement={placement or 'default'}"
        with phase(label):
            res = pipe.run_sharded(plan=plan)
            jax.block_until_ready([r.array for r in res.columns.values()])
        if not plan.shards:
            raise AssertionError(f"{label}: the plan sharded no column")
        check_equal(label, {n: (res[n].array, ref[n]) for n in cols})
        for col, specs in plan.shards.items():
            want = tuple(int(plan.device_ids[plan.final_device(s.name)])
                         for s in specs)
            got = res[col].shard_devices
            if got != want:
                raise AssertionError(f"{label} {col}: shards on {got}, "
                                     f"plan places them on {want}")
            # only a placement pins shard i to chip i; without one the
            # planner may land two shards of a column on one chip
            if placement and len(set(got)) != n_chips:
                raise AssertionError(f"{label} {col}: shards on {got}, not "
                                     f"on {n_chips} distinct devices")
        legs = {it for it, _src, _dst in plan.redistribution}
        if set(res.d2d_copies) != legs:
            raise AssertionError(f"{label}: executed D2D legs "
                                 f"{sorted(res.d2d_copies)} != planned "
                                 f"{sorted(legs)}")
        busy = sorted(d for d, items in res.per_device.items() if items)
        if len(busy) != n_chips:
            raise AssertionError(f"{label}: work on devices {busy}, not on "
                                 f"all {n_chips}")
        print(f"  {label}: bit-exact vs one chip; plan {plan.policy}, "
              "sharded " + (", ".join(f"{c}->{res[c].shard_devices}"
                                      for c in plan.shards) or "none")
              + f"; devices with work {busy}; D2D legs {len(legs)} planned, "
              f"{len(res.d2d_copies)} executed", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only run_sharded over four chips and its "
                         "one-chip comparison")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow a non-TPU platform and shrink the data")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        raise SystemExit(f"no TPU: JAX reports platform {dev.platform!r} "
                         "(pass --cpu-rehearsal to rehearse elsewhere)")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX has {len(devices)}")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    phase = Phases(dev)
    t0 = time.perf_counter()
    cols = make_columns(args, phase)
    if args.chips > 1:
        sharded(cols, phase, args.chips)
    else:
        stream_and_queries(cols, phase)
    print(f"total {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
