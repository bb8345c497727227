"""Decode programs are named by their kind and their graph's pattern, so a
device trace tells the patterns apart: ``np`` if any stage is Non-Parallel,
else ``gp`` if any is Group-Parallel, else ``fp``.  The name depends on
nothing else, so same-signature graphs still share one program."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as P
from repro.core.compiler import (ProgramCache, build_graph, device_buffers,
                                 pattern_of)
from repro.core.query import lower_query
from repro.data.columns import TABLE2_PLANS
from repro.data.queries import Q6_PLAN
from repro.data.tpch import generate

mp = P.make_plan


def _rle(rng, n=400):
    return np.repeat(rng.integers(0, 50, n),
                     rng.integers(1, 40, n)).astype(np.int32)


CASES = {
    "fp": (mp("bitpack"), lambda rng: rng.integers(0, 999, 5000)),
    "gp": (mp("rle"), _rle),
    "np": (mp("ans"), lambda rng: rng.integers(0, 4, 20_000)),
}


@pytest.mark.parametrize("pattern", sorted(CASES))
def test_whole_decode_module_is_named_by_pattern(pattern, rng):
    plan, make = CASES[pattern]
    enc = P.encode(plan, np.asarray(make(rng)).astype(np.int32))
    graph = build_graph(enc)
    assert pattern_of(graph) == pattern
    prog = ProgramCache().get(graph)
    assert prog.fn.__name__ == f"decode_{pattern}"
    text = prog.fn.lower(device_buffers(enc)).as_text()
    assert f"@jit_decode_{pattern}" in text


def test_each_program_kind_has_its_own_name(rng):
    cache = ProgramCache()
    fp = build_graph(P.encode(mp("bitpack"),
                              rng.integers(0, 999, 5000).astype(np.int32)))
    key = build_graph(P.encode(mp("rle"), _rle(rng)))
    ans = build_graph(P.encode(mp("ans"),
                               rng.integers(0, 4, 20_000).astype(np.int32)))
    assert cache.get_chunk(fp, 1024).fn.__name__ == "decode_chunk_fp"
    assert (cache.get_group_chunk(key, 4, 512).fn.__name__
            == "decode_span_gp")
    assert cache.get_group_prologue(key).fn.__name__ == "decode_prologue_gp"
    assert (cache.get_group_chunk(ans, 1, 4096).fn.__name__
            == "decode_span_np")
    prog = cache.get(fp)
    stacked = {k: jnp.stack([v, v]) for k, v in device_buffers(
        P.encode(mp("bitpack"), rng.integers(0, 999, 5000).astype(np.int32))
    ).items()}
    prog.batched(stacked)
    assert prog._batched.__name__ == "decode_batched_fp"


def test_fused_query_chunk_counts_no_reduce_stage():
    cols = generate(scale=0.001, seed=0)
    encs = {c: P.encode(TABLE2_PLANS[c], cols[c]) for c in Q6_PLAN.columns()}
    fq = lower_query(Q6_PLAN, encs)
    assert pattern_of(fq.graph) == "fp"
    prog = ProgramCache().get_query_chunk(fq.graph, 4096)
    assert prog.fn.__name__ == "query_chunk_fp"


def test_same_signature_graphs_share_one_named_program(rng):
    cache = ProgramCache()
    a, b = (build_graph(P.encode(mp("bitpack"),
                                 rng.integers(0, 999, 5000).astype(np.int32)))
            for _ in range(2))
    assert a.signature == b.signature
    assert cache.get(a) is cache.get(b)
    assert cache.stats == {"programs": 1, "hits": 1, "misses": 1,
                           "evictions": 0}
    assert cache.get(a).fn.__name__ == "decode_fp"
