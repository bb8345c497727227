"""Training substrate: optimizer descent, fault-tolerant checkpointing (atomic,
hash-verified, compressed), restart-from-failure, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.models import get_model
from repro.train import checkpoint as ckpt
from repro.train.loop import LoopConfig, SimulatedFailure, run
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import make_train_step


def _setup(arch="qwen1.5-0.5b"):
    cfg = SMOKES[arch]
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=50,
                         weight_decay=0.0)
    from repro.train import optimizer
    opt_state = optimizer.init(params)
    step = jax.jit(make_train_step(cfg, opt_cfg, remat=None))

    def batch_fn(i):
        rng = np.random.default_rng(i)  # deterministic in step
        toks = rng.integers(0, cfg.vocab, (2, 33))
        return {"tokens": jnp.asarray(toks[:, :-1], dtype=jnp.int32),
                "labels": jnp.asarray(toks[:, 1:], dtype=jnp.int32)}

    return cfg, params, opt_state, step, batch_fn


def test_loss_decreases():
    _, params, opt, step, batch_fn = _setup()
    batch = batch_fn(0)  # overfit one batch
    losses = []
    for _ in range(12):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_checkpoint_roundtrip(tmp_path):
    _, params, opt, step, batch_fn = _setup()
    params, opt, _ = step(params, opt, batch_fn(0))
    d = str(tmp_path / "ck")
    ckpt.save(d, 7, (params, opt), extra={"note": "x"})
    (p2, o2), step_no, extra = ckpt.restore(d, (params, opt))
    assert step_no == 7 and extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rep = ckpt.compression_report(d)
    assert rep["ratio"] > 1.0, rep  # exponent-plane ANS actually compresses


def test_checkpoint_corruption_detected(tmp_path):
    _, params, opt, *_ = _setup()
    d = str(tmp_path / "ck")
    sdir = ckpt.save(d, 1, params)
    victim = [f for f in os.listdir(sdir) if f.endswith(".npz")][0]
    with open(os.path.join(sdir, victim), "r+b") as f:
        f.seek(100)
        f.write(b"\xde\xad")
    with pytest.raises(IOError, match="corruption"):
        ckpt.restore(d, params)


def test_loop_restart_after_failure(tmp_path):
    """Crash at step 5, restart, converge to the same final state as an
    uninterrupted run (deterministic batches)."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    # uninterrupted reference
    _, params, opt, step, batch_fn = _setup()
    cfg_ref = LoopConfig(total_steps=8, ckpt_dir=d1, ckpt_every=2, log_every=100)
    p_ref, o_ref, hist = run(cfg_ref, step, params, opt, batch_fn,
                             log=lambda s: None)
    # crashing run
    _, params, opt, step, batch_fn = _setup()
    cfg_fail = LoopConfig(total_steps=8, ckpt_dir=d2, ckpt_every=2,
                          log_every=100, fail_at_step=5)
    with pytest.raises(SimulatedFailure):
        run(cfg_fail, step, params, opt, batch_fn, log=lambda s: None)
    # restart resumes from step 4 checkpoint and finishes
    cfg_resume = LoopConfig(total_steps=8, ckpt_dir=d2, ckpt_every=2,
                            log_every=100)
    _, params2, opt2, step, batch_fn = _setup()
    p_fin, o_fin, hist2 = run(cfg_resume, step, params2, opt2, batch_fn,
                              log=lambda s: None)
    assert hist2[0]["step"] == 4  # resumed, not restarted from scratch
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_fin)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_grad_compression_unbiased_convergence():
    """int8 error-feedback psum: a quadratic objective still converges, and the
    wire format is 4x smaller."""
    from repro.train import grad_compress as gc

    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    target = jnp.asarray(np.random.default_rng(0).normal(size=(64,)),
                         jnp.float32)

    def one_step(w, err):
        g = 2 * (w - target)
        gsum, err = gc.compressed_psum(g, err, "pod")
        return w - 0.05 * gsum, err

    stepped = jax.jit(jax.shard_map(
        one_step, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),) * 2,
        out_specs=(jax.sharding.PartitionSpec(),) * 2, check_vma=False))
    w = jnp.zeros((64,))
    err = jnp.zeros((64,))
    for _ in range(200):
        w, err = stepped(w, err)
    np.testing.assert_allclose(np.asarray(w), np.asarray(target), atol=1e-2)
    assert gc.wire_bytes({"w": w}, compressed=True) * 4 == \
        gc.wire_bytes({"w": w}, compressed=False)


def test_quantize_int8_roundtrip_error():
    from repro.train.grad_compress import dequantize, quantize_int8

    x = jnp.asarray(np.random.default_rng(1).normal(size=(1000,)), jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6
