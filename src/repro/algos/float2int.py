"""Float2Int (paper §2.1, Fully-Parallel family; the ALP/G-ALP idea).

Encode: find the smallest decimal scale 10^d such that the integer k = round(x * 10^d)
reconstructs x exactly; store the integers (bit-packable child slot) plus a sparse
exception list for values that do not round-trip.  Decode (F.P.): split |k| into whole
and fractional parts, q = |k| // 10^d and r = |k| - q * 10^d, add q to the float32
fraction table entry frac[r] = r / 10^d and give the sum k's sign (negation is exact,
so negative values decode as their magnitudes do); then scatter-patch exceptions
(Aux; rare -> cheap).

The decode uses only integer arithmetic, exact int->float conversion, a table read,
one float32 add and a sign flip, each of which every backend computes identically.
A float32 division k / 10^d would not do: the TPU's divide is not correctly rounded,
and a multiply by 1 / 10^d misses the true quotient for about a quarter of 2-decimal
prices.  The encoder checks each value with the decoder's arithmetic, so any value the
split form cannot reproduce becomes an exception and decode stays bit-exact.
"""
from __future__ import annotations

from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core.patterns import Aux, BufSpec, Ctx, FullyParallel, primary
from repro.core.registry import register

# the fraction table holds 10^d entries, so d stays small
_MAX_DECIMALS = 4


def _split_decode(ints: np.ndarray, d: int) -> np.ndarray:
    """Host mirror of the device decode: the magnitude |k| splits into
    float32(|k| // 10^d) + frac[|k| % 10^d], then takes k's sign.  ``ints``
    holds integers below 2^31 (as int64 or float64): float64 floor division
    and remainder are exact there, and faster than int64."""
    k = np.asarray(ints, np.float64)
    a = np.abs(k)
    q = np.floor(a / 10 ** d)
    r = (a - q * 10 ** d).astype(np.int64)
    m = q.astype(np.float32) + _frac_table(d)[r]
    return np.where(k < 0, -m, m)


def _frac_table(d: int) -> np.ndarray:
    return (np.arange(10 ** d, dtype=np.float64) / 10 ** d).astype(np.float32)


class Float2IntCodec:
    name = "float2int"
    pattern = "fp"

    def encode(self, arr: np.ndarray, decimals: int | None = None,
               **_: Any) -> tuple[dict[str, np.ndarray], dict]:
        flat = np.asarray(arr).reshape(-1).astype(np.float64)
        flat32 = flat.astype(np.float32)

        def attempt(d: int):
            scaled = np.round(flat * 10.0**d)
            ok = np.abs(scaled) < 2**31 - 1
            # exactness is verified with the decoder's own arithmetic, bit for
            # bit (so -0.0, which decodes as +0.0, is an exception)
            recon = _split_decode(np.where(ok, scaled, 0), d)
            return scaled, ok & (recon.view(np.uint32) == flat32.view(np.uint32))

        best = None
        cand = range(_MAX_DECIMALS + 1) if decimals is None else [decimals]
        for d in cand:
            scaled, exact = attempt(d)
            n_exc = int((~exact).sum())
            if best is None or n_exc < best[0]:
                best = (n_exc, d, scaled, exact)
            if n_exc == 0:
                break
        _, d, scaled, exact = best
        exc_idx = np.flatnonzero(~exact).astype(np.int32)
        ints = np.where(exact, scaled, 0).astype(np.int64)
        # the scale ships as a (1,) runtime buffer beside its fraction table
        return ({"ints": ints,
                 "exc_idx": exc_idx,
                 "exc_val": flat[exc_idx].astype(np.float32),
                 "scale": np.asarray([10 ** d], np.int32),
                 "frac": _frac_table(d)},
                {"decimals": int(d), "n_exc": int(exc_idx.size)})

    def decode_np(self, bufs: dict[str, np.ndarray], meta: dict, n: int,
                  dtype: Any) -> np.ndarray:
        out = _split_decode(bufs["ints"], meta["decimals"])
        out[np.asarray(bufs["exc_idx"]).astype(np.int64)] = np.asarray(bufs["exc_val"])
        return out.astype(dtype)

    def stages(self, enc, buf_names: dict[str, str], out_name: str,
               meta_names: dict[str, str] | None = None) -> list:
        n_exc = int(enc.meta["n_exc"])
        mid = f"{out_name}.scaled" if n_exc else out_name

        def fn(ctx: Ctx, ints: jnp.ndarray, scale: jnp.ndarray,
               frac: jnp.ndarray) -> jnp.ndarray:
            v = primary(ctx, ints).astype(jnp.int32)
            a = jnp.abs(v)
            q = a // scale[0]
            m = q.astype(jnp.float32) + frac[a - q * scale[0]]
            return jnp.where(v < 0, -m, m)

        stages: list = [FullyParallel(
            fn=fn, inputs=(buf_names["ints"], buf_names["scale"],
                           buf_names["frac"]),
            specs=(BufSpec("tile"), BufSpec("full"), BufSpec("full")),
            out=mid, n_out=enc.n, out_dtype=jnp.float32,
            elementwise=True, name="f2i-scale")]
        if n_exc:
            def patch(x: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray):
                return x.at[idx].set(val)

            stages.append(Aux(
                fn=patch, inputs=(mid, buf_names["exc_idx"], buf_names["exc_val"]),
                out=out_name, n_out=enc.n, out_dtype=jnp.float32, name="f2i-patch"))
        return stages


register(Float2IntCodec())
