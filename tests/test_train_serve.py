"""Training substrate + serving: optimizer math, checkpoint/resume,
compression error feedback, data determinism, straggler policy, serve
engine, paged KV."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.transformer import ModelConfig, init_params
from repro.serve import DecodeServeEngine, PagedAllocator, Request
from repro.train import AdamWConfig, TrainConfig, checkpoint, make_train_step
from repro.train.data import DataConfig, markov_batch, select_corpus_samples, synthetic_batch
from repro.train.optimizer import apply_updates, init_state, schedule
from repro.train.straggler import StragglerMonitor, StragglerPolicy, reshard_plan
from repro.train.trainer import init_train_state, xent_loss
from repro.relational.relation import Relation

CFG = ModelConfig(name="t", num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                  d_ff=64, vocab=64, compute_dtype="float32", remat=False)


def test_adamw_matches_reference_step():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                      clip_norm=1e9, warmup_steps=0, total_steps=10, min_lr_frac=1.0)
    params = {"w": jnp.array([1.0, -2.0])}
    grads = {"w": jnp.array([0.5, 0.5])}
    state = init_state(cfg, params)
    new_p, state, _ = apply_updates(cfg, params, grads, state)
    m = 0.1 * 0.5 / (1 - 0.9)
    v = 0.01 * 0.25 / (1 - 0.99)
    want = 1.0 - 0.1 * m / (np.sqrt(v) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"])[0], want, rtol=1e-5)


def test_schedule_warmup_and_cosine():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110, min_lr_frac=0.1)
    assert float(schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(schedule(cfg, jnp.int32(10))) == pytest.approx(1.0, rel=1e-3)
    assert float(schedule(cfg, jnp.int32(110))) == pytest.approx(0.1, rel=1e-3)


def test_grad_clipping_caps_norm():
    from repro.train.optimizer import clip_by_global_norm

    g = {"a": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


def test_xent_loss_masking():
    logits = jnp.zeros((1, 3, 5))
    labels = jnp.array([[1, -100, 2]])
    loss = xent_loss(logits, labels)
    assert float(loss) == pytest.approx(np.log(5), rel=1e-5)


@pytest.mark.slow
def test_train_loss_decreases_markov():
    tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=60))
    params, opt = init_train_state(jax.random.PRNGKey(0), CFG, tcfg)
    step = jax.jit(make_train_step(CFG, tcfg), donate_argnums=(0, 1))
    dcfg = DataConfig(vocab=CFG.vocab, seq_len=32, global_batch=8)
    losses = []
    for i in range(60):
        batch = jax.tree.map(jnp.asarray, markov_batch(dcfg, i))
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.2


@pytest.mark.slow
def test_microbatch_accumulation_matches_full_batch():
    adamw = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    params, opt = init_train_state(jax.random.PRNGKey(0), CFG, TrainConfig(adamw=adamw))
    batch = jax.tree.map(jnp.asarray, synthetic_batch(DataConfig(64, 16, 8), 0))
    p1, _, m1 = make_train_step(CFG, TrainConfig(adamw=adamw, microbatches=1))(params, opt, batch)
    p2, _, m2 = make_train_step(CFG, TrainConfig(adamw=adamw, microbatches=4))(params, opt, batch)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_checkpoint_roundtrip_and_latest():
    params = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3), "b": jnp.ones(3, jnp.bfloat16)}
    with tempfile.TemporaryDirectory() as d:
        assert checkpoint.latest_step(d) is None
        checkpoint.save(d, 5, params)
        checkpoint.save(d, 10, params)
        assert checkpoint.latest_step(d) == 10
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        restored = checkpoint.restore(d, 10, like)
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_checkpoint_shape_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, 1, {"w": jnp.zeros((2, 2))})
        with pytest.raises(ValueError):
            checkpoint.restore(d, 1, {"w": jax.ShapeDtypeStruct((3, 3), jnp.float32)})


def test_data_stream_deterministic_and_elastic():
    dcfg = DataConfig(vocab=100, seq_len=8, global_batch=8)
    a = synthetic_batch(dcfg, 3, host=0, num_hosts=2)
    b = synthetic_batch(dcfg, 3, host=0, num_hosts=2)
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    c = synthetic_batch(dcfg, 3, host=1, num_hosts=2)
    assert not np.array_equal(a["inputs"], c["inputs"])
    plan = reshard_plan(4, 8, 256)
    assert plan["per_host_batch"] == 32


COMPRESSION_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.train.compression import compressed_psum, init_error
mesh = jax.make_mesh((4,), ("data",))
g = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.3}
def f(gl, e):
    out, e2 = compressed_psum(gl, e, "data")
    return out, e2
fn = jax.jit(jax.shard_map(f, mesh=mesh,
    in_specs=(jax.sharding.PartitionSpec("data"), jax.sharding.PartitionSpec("data")),
    out_specs=(jax.sharding.PartitionSpec("data"), jax.sharding.PartitionSpec("data"))))
err = {"w": jnp.zeros((4, 8), jnp.float32)}
out, err2 = fn(g, err)
# mean over 4 shards of per-shard rows, approx: compare with exact psum/4
exact = np.stack([np.asarray(g["w"])[i::1] for i in range(1)]).mean(0)
# each shard holds 1 row; psum/4 = mean of the 4 rows broadcast back
want = np.tile(np.asarray(g["w"]).reshape(4, 8).mean(0), (4, 1))
got = np.asarray(out["w"])
assert np.abs(got - want).max() < 0.02, (got[0], want[0])
# error feedback: residual equals x - dequant
assert np.isfinite(np.asarray(err2["w"])).all()
print("COMP_OK")
"""


@pytest.mark.slow
def test_compressed_psum_subprocess():
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",  # fake CPU devices by design, never an accelerator
        "PYTHONPATH": "src",
    }
    res = subprocess.run([sys.executable, "-c", COMPRESSION_SCRIPT], capture_output=True,
                         text=True, env=env, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "COMP_OK" in res.stdout, res.stderr[-2000:]


def test_compression_error_feedback_converges():
    # repeated compression of a constant gradient: mean of dequantized
    # values over steps converges to the true value (error feedback)
    from repro.train.compression import _quantize

    x = np.float32(0.013)
    scale = np.float32(1.0 / 127.0)
    err = np.float32(0.0)
    outs = []
    for _ in range(50):
        q = float(_quantize(jnp.float32(x + err), jnp.float32(scale)))
        deq = q * scale
        err = x + err - deq
        outs.append(deq)
    assert abs(np.mean(outs) - x) < 1e-4


def test_straggler_monitor_evicts_persistent_offender():
    mon = StragglerMonitor(4, StragglerPolicy(slow_factor=1.5, min_flags=3, restart_cost_steps=10))
    evicted = []
    for _ in range(5):
        r = mon.observe(np.array([1.0, 1.0, 1.0, 3.0]))
        evicted += r["evict"]
    assert 3 in evicted
    r = mon.observe(np.array([1.0, 1.0, 1.0, 1.0]))
    assert r["slow"] == []


def test_serve_engine_completes_all_requests():
    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = DecodeServeEngine(params, CFG, slots=3, max_len=32)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, 64, 3).astype(np.int32), max_new=4) for i in range(5)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    assert all(len(r.out) >= 4 for r in reqs)


def test_paged_allocator_lookup_and_release():
    pa = PagedAllocator(num_pages=16, page_size=8)
    pa.alloc(1, 20)  # 3 pages
    pa.alloc(2, 8)  # 1 page
    slots = pa.lookup(np.array([1, 1, 1, 2, 9]), np.array([0, 1, 2, 0, 0]))
    assert (slots[:4] >= 0).all() and slots[4] == -1
    assert len(set(slots[:4].tolist())) == 4
    pa.release(1)
    assert pa.lookup(np.array([1]), np.array([0]))[0] == -1
    with pytest.raises(MemoryError):
        pa.alloc(3, 16 * 8 + 1)


def test_corpus_selection_relational():
    n = 1000
    rng = np.random.default_rng(0)
    docs = Relation(
        "Docs", {"doc": np.arange(n), "shard": rng.integers(0, 4, n), "lang": rng.integers(0, 3, n)}
    )
    quality = Relation("Quality", {"doc": np.arange(n), "score": rng.integers(0, 100, n)})
    dedup = Relation("Dedup", {"doc": np.arange(n), "canonical": np.arange(n)})
    keep = select_corpus_samples(docs, quality, dedup, min_quality=50)
    scores = np.asarray(quality.columns["score"])
    want = np.flatnonzero(scores >= 50)
    np.testing.assert_array_equal(keep, want)
