"""Roundtrip correctness for every codec and nesting, three decode paths:
numpy oracle, pure-jnp stages (fused + unfused).  Property-based via hypothesis."""
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import plan as P
from repro.core.compiler import compile_decoder, device_buffers

mp = P.make_plan


def roundtrip(pl, arr, backends=("jnp",)):
    enc = P.encode(pl, arr)
    out = P.decode_np(enc)
    np.testing.assert_array_equal(out, arr, err_msg="numpy oracle")
    bufs = device_buffers(enc)
    for backend in backends:
        for fuse in (False, True):
            dec = compile_decoder(enc, backend=backend, fuse=fuse)
            got = np.asarray(dec(bufs))
            np.testing.assert_array_equal(got, arr,
                                          err_msg=f"{backend} fuse={fuse}")
    return enc


ints = st.integers(min_value=-2**30, max_value=2**30)


@settings(max_examples=25, deadline=None)
@given(st.lists(ints, min_size=1, max_size=300))
def test_bitpack_roundtrip(xs):
    roundtrip(mp("bitpack"), np.asarray(xs, np.int32))


@settings(max_examples=25, deadline=None)
@given(st.lists(ints, min_size=1, max_size=300))
def test_delta_bitpack_roundtrip(xs):
    roundtrip(P.Plan("delta", children={"deltas": mp("bitpack")}),
              np.asarray(xs, np.int32))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 9), min_size=1, max_size=200),
       st.lists(st.integers(1, 30), min_size=1, max_size=200))
def test_rle_roundtrip(vals, counts):
    n = min(len(vals), len(counts))
    arr = np.repeat(np.asarray(vals[:n], np.int32), counts[:n])
    if arr.size == 0:
        return
    enc = roundtrip(P.Plan("rle", children={"counts": mp("bitpack"),
                                            "values": mp("bitpack")}), arr)
    assert enc.meta["n_groups"] <= n


@settings(max_examples=25, deadline=None)
@given(st.lists(st.sampled_from([3, 7, 11, -2, 1000]), min_size=1, max_size=400))
def test_dictionary_roundtrip(xs):
    roundtrip(P.Plan("dictionary", children={"index": mp("bitpack")}),
              np.asarray(xs, np.int32))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 10**7), min_size=1, max_size=300),
       st.integers(0, 3))
def test_float2int_roundtrip(ks, d):
    arr = (np.asarray(ks, np.int64) / 10.0**d).astype(np.float32)
    roundtrip(P.Plan("float2int", children={"ints": mp("bitpack")}), arr)


def test_float2int_decode_is_exact_on_any_backend():
    """2-decimal prices decode with no exceptions and no float divide: a TPU
    divide is not correctly rounded, so a divide in the decode would make
    device output differ from the source there."""
    import jax

    k = np.arange(90_000, 10_500_000, 997, dtype=np.int64)
    arr = (k / 100).astype(np.float32)
    enc = roundtrip(P.Plan("float2int", children={"ints": mp("bitpack")}),
                    arr)
    assert enc.meta["decimals"] == 2 and enc.meta["n_exc"] == 0
    prog = compile_decoder(enc).program
    jaxpr = jax.make_jaxpr(prog.raw_fn)(device_buffers(enc))
    float_divs = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "div"
                  and np.issubdtype(e.outvars[0].aval.dtype, np.floating)]
    assert not float_divs


@pytest.mark.parametrize("lo, hi", [(-10_500_000, -90_000), (-100, 0)])
def test_float2int_negative_decimals_have_no_exceptions(lo, hi):
    """Negative 2-decimal values, large and below one in magnitude, decode
    through the sign-magnitude split with no exceptions; -0.0 keeps its sign
    bit as an exception."""
    k = np.arange(lo, hi, max(1, (hi - lo) // 10_000), dtype=np.int64)
    arr = (k / 100).astype(np.float32)
    enc = roundtrip(P.Plan("float2int", children={"ints": mp("bitpack")}),
                    arr)
    assert enc.meta["decimals"] == 2 and enc.meta["n_exc"] == 0
    neg_zero = np.array([-0.0, 1.5], np.float32)
    enc = roundtrip(P.Plan("float2int", children={"ints": mp("bitpack")}),
                    neg_zero)
    assert enc.meta["n_exc"] == 1
    assert (P.decode_np(enc).view(np.uint32) == neg_zero.view(np.uint32)).all()


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 10**6), st.integers(-5, 5),
                          st.integers(1, 50)), min_size=1, max_size=50))
def test_deltastride_roundtrip(runs):
    parts = [start + stride * np.arange(count, dtype=np.int64)
             for start, stride, count in runs]
    arr = np.concatenate(parts).astype(np.int32)
    roundtrip(mp("deltastride"), arr)


@settings(max_examples=15, deadline=None)
@given(st.binary(min_size=1, max_size=2000))
def test_ans_roundtrip_bytes(data):
    arr = np.frombuffer(data, np.uint8).copy()
    roundtrip(P.Plan("ans", params={"chunk_size": 256}), arr)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(-100, 100), min_size=1, max_size=500))
def test_ans_roundtrip_int32(xs):
    roundtrip(P.Plan("ans", params={"chunk_size": 512}),
              np.asarray(xs, np.int32))


@settings(max_examples=15, deadline=None)
@given(st.text(alphabet="abcdef .", min_size=1, max_size=800))
def test_stringdict_roundtrip(text):
    arr = np.frombuffer(text.encode(), np.uint8).copy()
    if arr.size == 0:
        return
    roundtrip(P.Plan("stringdict", children={"index": mp("bitpack")}), arr)


def test_table2_plans_roundtrip():
    """Every paper-Table-2 plan roundtrips on the synthetic TPC-H columns."""
    from repro.data.columns import TABLE2_PLANS
    from repro.data.tpch import generate

    cols = generate(scale=0.002, seed=1)
    for name, pl in TABLE2_PLANS.items():
        enc = P.encode(pl, cols[name])
        out = P.decode_np(enc)
        np.testing.assert_array_equal(out, cols[name], err_msg=name)
        dec = compile_decoder(enc, backend="jnp", fuse=True)
        got = np.asarray(dec(device_buffers(enc)))
        np.testing.assert_array_equal(got, cols[name], err_msg=name + " jnp")


def test_compression_ratio_sanity():
    """Table-2 plans actually compress the TPC-H-shaped data."""
    from repro.data.columns import TABLE2_PLANS
    from repro.data.tpch import generate

    cols = generate(scale=0.005, seed=2)
    total_plain = total_comp = 0
    for name, pl in TABLE2_PLANS.items():
        enc = P.encode(pl, cols[name])
        total_plain += enc.plain_nbytes
        total_comp += enc.compressed_nbytes
    assert total_plain / total_comp > 2.5, \
        f"aggregate ratio too low: {total_plain / total_comp:.2f}"


def test_auto_plan_chooser():
    from repro.data.columns import auto_plan
    from repro.data.tpch import generate

    cols = generate(scale=0.002, seed=3)
    pl, ratio = auto_plan(cols["O_ORDERKEY"])
    assert ratio > 4, f"auto plan failed to find a good plan ({ratio:.1f})"
    enc = P.encode(pl, cols["O_ORDERKEY"])
    np.testing.assert_array_equal(P.decode_np(enc), cols["O_ORDERKEY"])
