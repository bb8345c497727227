"""The compressed store: a hit gives the blobs a fresh encode gives, the key
follows the seed and the encoder's sources, and the workers that encode
initialise no JAX backend."""
import os
import pickle

import numpy as np

from bench import store
from bench.tpch import gen

CFG = {"name": "store-test", "columns": ["L_SHIPDATE", "L_RETURNFLAG"],
       "block_scale": 0.002, "blocks": 2}


def _ensure(root, seed):
    return store.ensure(CFG, (0.002, 2, seed), seed, root, log=lambda m: None)


def test_hit_equals_fresh_encode_and_workers_touch_no_device(tmp_path):
    from repro.core.plan import encode
    from repro.data.columns import TABLE2_PLANS

    d, rep = _ensure(tmp_path, 7)
    assert not rep["hit"]
    assert [r["backends"] for r in rep["workers"]] == [[], []]
    d2, rep2 = _ensure(tmp_path, 7)
    assert rep2["hit"] and d2 == d
    cols = gen.generate(CFG["columns"], 0.002, 2, 7)
    stored = store.load(d2, CFG["columns"])
    for c in CFG["columns"]:
        fresh = encode(TABLE2_PLANS[c], cols[c])
        assert pickle.dumps(stored[c].meta) == pickle.dumps(fresh.meta)
        for k, v in fresh.buffers.items():
            np.testing.assert_array_equal(stored[c].buffers[k], v)
        assert stored[c].compressed_nbytes == fresh.compressed_nbytes


def test_key_follows_seed_config_and_encoder_sources(tmp_path):
    k = store.store_key(CFG, 7, "abc")
    assert k != store.store_key(CFG, 8, "abc")
    assert k != store.store_key(CFG, 7, "abd")
    assert k != store.store_key({**CFG, "columns": ["L_TAX"]}, 7, "abc")
    assert k != store.store_key({**CFG, "blocks": 3}, 7, "abc")
    assert k != store.store_key({**CFG, "fixed_streams": ["flag"]}, 7,
                                "abc")
    # what does not change the blobs does not change the key
    assert k == store.store_key({**CFG, "limits": {"q1_rel_err": 1}}, 7,
                                "abc")
    src = tmp_path / "src"
    src.mkdir()
    f = src / "codec.py"
    f.write_text("x = 1\n")
    d1 = store.source_digest((src,))
    f.write_text("x = 2\n")
    assert store.source_digest((src,)) != d1
    assert len(store.source_digest()) == 16


def test_prune_keeps_the_newest_stores(tmp_path):
    parent = tmp_path / "cfg"
    for i in range(store.KEEP + 2):
        d = parent / f"s{i}"
        d.mkdir(parents=True)
        (d / "done.json").write_text("{}")
        os.utime(d / "done.json", (i, i))
    store._prune(parent, keep=parent / "s0")
    left = sorted(p.name for p in parent.iterdir())
    assert "s0" in left and "s1" not in left
    assert len(left) == store.KEEP + 1
