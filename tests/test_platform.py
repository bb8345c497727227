"""No silent fallbacks between the code and the platform it runs on: the chip
table is chosen from the TPU's ``device_kind``, a mesh plan never folds onto
fewer devices, the Pallas backend never guesses ``interpret``, and the compile
cache follows ``JAX_COMPILATION_CACHE_DIR``."""
import types

import jax
import numpy as np
import pytest

from repro import compile_cache
from repro.core import geometry, planner
from repro.core import plan as P
from repro.core.compiler import ProgramCache, build_graph, compile_graph
from repro.core.executor import StreamingExecutor


def _fake_tpu(monkeypatch, kind):
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])


@pytest.mark.parametrize("kind,name", sorted(geometry.DEVICE_KINDS.items()))
def test_chip_resolved_from_device_kind(kind, name, monkeypatch):
    _fake_tpu(monkeypatch, kind)
    assert geometry.resolve_chip() == name
    assert geometry.chip().name == name
    # an explicit name is never overridden by the attached device
    assert geometry.resolve_chip("v4") == "v4"


def test_unknown_device_kind_raises(monkeypatch):
    _fake_tpu(monkeypatch, "TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        geometry.resolve_chip()
    with pytest.raises(ValueError, match="TPU v99"):
        StreamingExecutor(cache=ProgramCache())


def test_chip_off_tpu_and_by_name():
    assert jax.devices()[0].platform != "tpu"
    assert geometry.resolve_chip() == geometry.MODEL_CHIP
    assert StreamingExecutor(cache=ProgramCache()).chip == geometry.MODEL_CHIP
    assert StreamingExecutor(chip="v6e", cache=ProgramCache()).chip == "v6e"
    with pytest.raises(ValueError, match="unknown chip"):
        geometry.resolve_chip("v9z")


def test_mesh_plan_needing_more_devices_raises(rng):
    enc = P.encode(P.Plan("ans", params={"chunk_size": 512}),
                   rng.integers(0, 60, 40_000).astype(np.int32))
    ex = StreamingExecutor(chunk_bytes="auto", chunk_decode=True,
                           cache=ProgramCache())
    ex.compile("c", enc)
    n = jax.device_count() + 1
    mp = planner.plan_mesh_execution({"c": ex.column_profile("c")},
                                     ex.cost_model, n_devices=n,
                                     shard_threshold_bytes=0)
    with pytest.raises(ValueError, match="JAX has only"):
        ex.run_sharded(mp, {"c": enc})


def test_pallas_backend_needs_explicit_interpret(rng):
    graph = build_graph(P.encode(P.make_plan("bitpack"),
                                 rng.integers(0, 100, 500).astype(np.int32)))
    with pytest.raises(ValueError, match="interpret"):
        compile_graph(graph, backend="pallas")
    with pytest.raises(ValueError, match="interpret"):
        ProgramCache().get(graph, backend="pallas")
    assert compile_graph(graph, backend="pallas", interpret=True).backend \
        == "pallas"
    assert compile_graph(graph, backend="jnp").backend == "jnp"


def test_compile_cache_follows_env_var(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was    # left alone


def test_compile_cache_defaults_to_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "src"
                / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
