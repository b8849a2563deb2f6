"""Substrate tests: optimizer/trainer convergence, serving engine,
data pipeline determinism, checkpoint atomic/round-trip, fault tolerance,
gradient compression."""

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.data import TokenPipeline
from repro.models import transformer
from repro.train import (adamw_init, adamw_update, make_train_state,
                         make_train_step, warmup_cosine)
from repro.train.grad_compress import compressed_psum, init_error_state


def test_adamw_converges_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, _ = adamw_update(params, g, state, 0.05,
                                        weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_warmup_cosine_shape():
    lr = warmup_cosine(1.0, 10, 100)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1.0) < 0.11
    assert float(lr(100)) < float(lr(50)) < float(lr(11))


def test_train_loop_loss_decreases():
    """qwen3-smoke on the Markov pipeline: loss must drop (integration)."""
    cfg = dataclasses.replace(get_smoke("qwen3-0.6b"),
                              compute_dtype="float32")
    from repro.core import compat
    mesh = compat.make_mesh((1,), ("data",))
    state = make_train_state(cfg, jax.random.PRNGKey(0))
    step_fn, build = make_train_step(cfg, mesh, base_lr=1e-2, warmup=5,
                                     total=120, remat=False, donate=False)
    pipe = TokenPipeline(vocab=cfg.vocab, batch=8, seq=32, seed=0)
    losses = []
    jstep = jax.jit(step_fn)
    with mesh:
        for i in range(60):
            tok, lab = pipe.batch_at(i)
            state, metrics = jstep(state, jnp.asarray(tok),
                                   jnp.asarray(lab), None)
            losses.append(float(metrics["loss"]))
    # steady descent from ln(256)=5.55 toward the ln(8)=2.08 entropy floor
    assert losses[-1] < losses[0] - 1.0, losses[::10]
    assert losses[-1] < min(losses[:10]), losses[::10]


def test_microbatch_accumulation_matches_full_batch():
    cfg = dataclasses.replace(get_smoke("llama3.2-3b"),
                              compute_dtype="float32")
    from repro.core import compat
    mesh = compat.make_mesh((1,), ("data",))
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    lab = jnp.roll(tok, -1, 1)
    s0 = make_train_state(cfg, jax.random.PRNGKey(0))
    full, _ = make_train_step(cfg, mesh, microbatches=1, remat=False,
                              donate=False)
    micro, _ = make_train_step(cfg, mesh, microbatches=4, remat=False,
                               donate=False)
    with mesh:
        s1, m1 = jax.jit(full)(s0, tok, lab, None)
        s2, m2 = jax.jit(micro)(s0, tok, lab, None)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     s1["params"], s2["params"])
    assert max(jax.tree.leaves(d)) < 1e-5
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5


def test_serving_engine_continuous_batching():
    from repro.serve import Engine
    cfg = dataclasses.replace(get_smoke("qwen3-0.6b"),
                              compute_dtype="float32")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, batch=2, max_len=64)
    rids = [eng.submit([1, 2, 3], max_new=5), eng.submit([4, 5], max_new=4),
            eng.submit([6], max_new=3)]
    done = eng.run()
    assert sorted(r.rid for r in done) == sorted(rids)
    assert [len(r.out) for r in sorted(done, key=lambda r: r.rid)] == [5, 4, 3]
    # determinism: greedy decode reproduces
    eng2 = Engine(cfg, params, batch=2, max_len=64)
    for r in sorted(done, key=lambda r: r.rid):
        eng2.submit(r.prompt, max_new=r.max_new)
    done2 = eng2.run()
    for a, b in zip(sorted(done, key=lambda r: r.rid),
                    sorted(done2, key=lambda r: r.rid)):
        assert a.out == b.out
    # rids stay unique after a drain: a later submit must not collide
    # with an already-completed request's id
    late = eng.submit([7, 8], max_new=2)
    assert late not in rids
    (r,) = eng.run()
    assert r.rid == late and len(r.out) == 2


def test_pipeline_determinism_and_structure():
    p1 = TokenPipeline(vocab=64, batch=4, seq=16, seed=3)
    p2 = TokenPipeline(vocab=64, batch=4, seq=16, seed=3)
    t1, l1 = p1.batch_at(7)
    t2, l2 = p2.batch_at(7)
    assert np.array_equal(t1, t2) and np.array_equal(l1, l2)
    assert np.array_equal(t1[:, 1:], l1[:, :-1])
    # host sharding: different hosts, different data
    ph = TokenPipeline(vocab=64, batch=4, seq=16, seed=3, n_hosts=2,
                       host_id=1)
    th, _ = ph.batch_at(7)
    assert not np.array_equal(t1, th)
    # resumability
    p1.restore({"step": 5})
    a = next(p1)
    assert np.array_equal(a[0], p2.batch_at(5)[0])


def test_checkpoint_roundtrip_and_keep(tmp_path):
    from repro.ckpt import latest_step, list_steps, restore, save
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.int32)}}
    for s in (1, 5, 9, 13):
        save(tmp_path, s, tree, keep=2)
    assert list_steps(tmp_path) == [9, 13]
    got, step = restore(tmp_path, tree)
    assert step == 13
    np.testing.assert_array_equal(got["a"], np.asarray(tree["a"]))
    np.testing.assert_array_equal(got["nested"]["b"],
                                  np.asarray(tree["nested"]["b"]))


def test_checkpoint_async(tmp_path):
    from repro.ckpt import restore, save
    tree = {"w": jnp.full((8, 8), 3.0)}
    t = save(tmp_path, 2, tree, blocking=False)
    t.join()
    got, _ = restore(tmp_path, tree)
    np.testing.assert_array_equal(got["w"], 3.0 * np.ones((8, 8)))


def test_restart_policy_resumes(tmp_path):
    from repro.ckpt import latest_step, restore, save
    from repro.ft import RestartPolicy, run_with_restarts
    crashes = {"n": 0}

    def loop(start):
        step = latest_step(tmp_path) or 0
        state = restore(tmp_path, {"x": jnp.zeros(())})[0] \
            if step else {"x": np.zeros(())}
        while step < 10:
            step += 1
            state = {"x": state["x"] + 1}
            save(tmp_path, step, state, keep=1)
            if step == 4 and crashes["n"] == 0:
                crashes["n"] += 1
                raise RuntimeError("simulated node failure")
        return step

    final = run_with_restarts(loop, policy=RestartPolicy(max_restarts=2))
    assert final == 10
    got, s = restore(tmp_path, {"x": jnp.zeros(())})
    assert s == 10 and float(got["x"]) == 10.0   # no lost/duplicated work


def test_straggler_watchdog():
    from repro.ft import StragglerWatchdog
    w = StragglerWatchdog(threshold=2.0)
    for _ in range(20):
        assert not w.record(1.0)
    assert w.record(5.0)          # 5x median -> flagged
    assert not w.record(1.1)


def test_compressed_psum_single_device_accuracy():
    """On a 1-device mesh the compressed psum must equal the plain value
    within int8 quantization error, and error feedback must push the
    *accumulated* estimate toward exact."""
    from repro.core import compat
    mesh = compat.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as P
    g = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 0.01

    def run(gg, err):
        return compressed_psum(gg, "d", err)

    f = jax.shard_map(run, mesh=mesh, in_specs=(P(), P()),
                      out_specs=(P(), P()))
    out, err = f(g, jnp.zeros_like(g))
    q_err = float(jnp.abs(out - g).max())
    assert q_err < 0.01 * 2 / 127 + 1e-6        # block absmax / 127
    # error feedback: sum of two steps of the SAME gradient ~ 2g exactly
    out2, _ = f(g, err)
    total_err = float(jnp.abs((out + out2) - 2 * g).max())
    assert total_err < q_err * 1.01
