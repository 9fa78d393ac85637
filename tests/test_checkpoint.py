"""Checkpointer: atomicity, keep-N GC, async, exact bf16 roundtrip,
restore-into-different-sharding (elastic path)."""
import os

import jax
from repro.launch.mesh import auto_mesh
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer


def _state(key=0):
    ks = jax.random.split(jax.random.key(key), 3)
    return {
        "params": {
            "w": jax.random.normal(ks[0], (8, 4)).astype(jnp.bfloat16),
            "b": jax.random.normal(ks[1], (4,)),
        },
        "opt": {"m": jax.random.normal(ks[2], (8, 4)), "step": jnp.int32(7)},
    }


def test_roundtrip_exact(tmp_path):
    ck = Checkpointer(str(tmp_path))
    state = _state()
    ck.save(3, state)
    step, restored = ck.restore(jax.eval_shape(lambda: state))
    assert step == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_and_wait(tmp_path):
    ck = Checkpointer(str(tmp_path))
    state = _state()
    ck.save(1, state, blocking=False)
    ck.wait()
    assert ck.latest_step() == 1


def test_keep_n_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_n=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _state(s))
    assert ck.all_steps() == [3, 4]


def test_atomic_no_partial_visible(tmp_path):
    """A tmp dir from a crashed save must never be listed as a step."""
    ck = Checkpointer(str(tmp_path))
    ck.save(5, _state())
    os.makedirs(tmp_path / "tmp.6")  # simulated crash mid-save
    (tmp_path / "tmp.6" / "arrays.npz").write_bytes(b"garbage")
    assert ck.all_steps() == [5]
    assert ck.latest_step() == 5


def test_restore_latest_and_specific(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_n=5)
    s1, s2 = _state(1), _state(2)
    ck.save(1, s1)
    ck.save(2, s2)
    tmpl = jax.eval_shape(lambda: s1)
    step, r = ck.restore(tmpl)
    assert step == 2
    step, r = ck.restore(tmpl, step=1)
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(r["params"]["b"]), np.asarray(s1["params"]["b"])
    )


def test_restore_with_shardings(tmp_path):
    """Elastic path: restore placing leaves with explicit shardings."""
    mesh = auto_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    ck = Checkpointer(str(tmp_path))
    state = _state()
    ck.save(1, state)
    shardings = jax.tree.map(lambda _: sh, state)
    _, restored = ck.restore(jax.eval_shape(lambda: state), shardings=shardings)
    assert restored["params"]["w"].sharding == sh


def test_resume_training_bit_exact(tmp_path):
    """Save at step k, keep training; restart from ckpt replays identically
    (deterministic data pipeline + pure train step)."""
    from repro.configs.base import ParallelConfig, RunConfig, TrainConfig
    from repro.configs.reduced import reduce_config
    from repro.core.placement import Env
    from repro.data.pipeline import DataConfig, host_batch
    from repro.models.registry import build_model
    from repro.training.trainer import make_train_step

    cfg = reduce_config("llama3.2-1b")
    model = build_model(cfg, Env())
    run = RunConfig(model=cfg, parallel=ParallelConfig(),
                    train=TrainConfig(lr=1e-3, warmup_steps=2, total_steps=20))
    init_state, train_step, _, _ = make_train_step(model, run)
    dc = DataConfig(vocab=cfg.vocab, seq_len=8, global_batch=4)
    step_fn = jax.jit(train_step)

    ck = Checkpointer(str(tmp_path))
    state = init_state(jax.random.key(0))
    for i in range(3):
        b = {k: jnp.asarray(v) for k, v in host_batch(dc, i, 0, 1).items()}
        state, _ = step_fn(state, b)
    ck.save(3, state)
    # continue to 6
    cont = state
    for i in range(3, 6):
        b = {k: jnp.asarray(v) for k, v in host_batch(dc, i, 0, 1).items()}
        cont, _ = step_fn(cont, b)
    # crash + restore + replay
    _, restored = ck.restore(jax.eval_shape(lambda: state))
    for i in range(3, 6):
        b = {k: jnp.asarray(v) for k, v in host_batch(dc, i, 0, 1).items()}
        restored, _ = step_fn(restored, b)
    for a, b_ in zip(jax.tree.leaves(cont["params"]), jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
