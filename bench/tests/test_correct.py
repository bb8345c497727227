"""``correct`` comes out false when the timed path is broken underneath, or
when the control (the reference a step below the stated precision) takes
the program's place.

Faults, each planted in the pipeline the window drives: an answer altered
where it is produced, and half of the rows left out with the sum over the
rest doubled.  One chip has no exchange between chips and no step state,
so those faults do not apply."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, readings

SEED = 4000000007


def run_in_process(workload, store_dir, hooks, capsys):
    code = harness.main(["--workload", workload, "--seed", str(SEED),
                         "--seconds", "0.2", "--trace", "0", "--rehearse",
                         "--store-dir", str(store_dir)], hooks=hooks)
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def alter_answer(pipe):
    orig = pipe.run_query

    def run_query(qplan, **kw):
        qe = orig(qplan, **kw)
        r = np.array(qe.result, np.float64)
        r.reshape(-1)[0] *= 1 + 1e-3
        qe.result = jnp.asarray(r, jnp.float32)
        return qe
    pipe.run_query = run_query


def drop_group(pipe):
    """Q1 loses a group (its count lane reads 0): the gap is infinite."""
    orig = pipe.run_query

    def run_query(qplan, **kw):
        qe = orig(qplan, **kw)
        if qplan.name == "q1":
            r = np.array(qe.result, np.float64)
            r[4, np.flatnonzero(r[4])[0]] = 0
            qe.result = jnp.asarray(r, jnp.float32)
        return qe
    pipe.run_query = run_query


def half_the_rows(pipe):
    """Each query runs over the first half of its rows, doubled."""
    from repro.core.plan import encode
    from repro.data.columns import TABLE2_PLANS
    from repro.data.loader import ColumnPipeline
    from repro.core.plan import decode_np
    from bench.store import attach

    half = ColumnPipeline(dict(pipe.plans))
    attach(half, {c: encode(TABLE2_PLANS[c], (a := decode_np(e))[:a.size // 2])
                  for c, e in pipe._encoded.items()})

    def run_query(qplan, **kw):
        qe = half.run_query(qplan, **kw)
        qe.result = qe.result * 2
        return qe
    pipe.run_query = run_query


def alter_value(pipe):
    orig = pipe.run

    def run(*a, **kw):
        res = orig(*a, **kw)
        rec = next(iter(res.values()))
        rec.array = rec.array.at[0].add(1)
        return res
    pipe.run = run


def half_the_stream(pipe):
    """The second half of every column is a copy of the first."""
    orig = pipe.run

    def run(*a, **kw):
        res = orig(*a, **kw)
        for rec in res.values():
            n = rec.array.shape[0]
            rec.array = jnp.concatenate([rec.array[:n // 2],
                                         rec.array[:n - n // 2]])
        return res
    pipe.run = run


@pytest.mark.parametrize("workload,fault", [
    ("sf10-q1q6.fused", None), ("sf10-q1q6.fused", alter_answer),
    ("sf10-q1q6.fused", drop_group), ("sf10-q1q6.fused", half_the_rows),
    ("sf10-q12cols.stream", None),
    ("sf10-q12cols.stream", alter_value),
    ("sf10-q12cols.stream", half_the_stream)])
def test_a_broken_timed_path_is_not_correct(workload, fault, store_dir,
                                            capsys):
    res = run_in_process(workload, store_dir, fault, capsys)
    assert res["correct"] is (fault is None), res["checks"]
    if fault is drop_group:
        assert res["checks"]["q1_rel_err"]["value"] == "inf"


@pytest.mark.parametrize("workload", ["sf10-q1q6.fused",
                                      "sf10-q12cols.stream"])
def test_control_fails_the_limits(workload, store_dir, capsys):
    """The control, planted where the program's answers come out, reads
    ``correct`` false through the whole run."""
    cell = harness.load_cell(harness.ROOT / "BENCHMARK.json", workload, False)
    cols = harness.source_columns(cell.config, SEED, rehearse=True)
    res = run_in_process(workload, store_dir,
                         readings.control_hook(cell, cols), capsys)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values()), \
        res["checks"]
