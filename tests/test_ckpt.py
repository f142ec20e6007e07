"""LSM checkpointing: roundtrip, merge-on-read, quorum, journal, reshard."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, CkptConfig, quorum_restore, reshard
from repro.ckpt.manager import corrupt_replica


def tiny_state(seed=0):
    k = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(k, (8, 4)),
              "layers": {"ln": jnp.ones((3, 4))}}
    opt = {"step": jnp.zeros((), jnp.int32),
           "m": jax.tree.map(jnp.zeros_like, params)}
    return params, opt


def trees_equal(a, b, atol=0.0):
    flat_a, flat_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.allclose(x, y, atol=atol) for x, y in zip(flat_a, flat_b))


def test_baseline_roundtrip(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path), replicas=3)
    mgr = CheckpointManager(cfg)
    params, opt = tiny_state()
    mgr.save_baseline(100, params, opt)
    out = quorum_restore(cfg, params, opt)
    assert out is not None
    p2, o2, step = out
    assert step == 100 and trees_equal(params, p2) and trees_equal(opt, o2)


def test_delta_merge_on_read(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path))
    mgr = CheckpointManager(cfg)
    params, opt = tiny_state()
    mgr.save_baseline(10, params, opt)
    newp = jax.tree.map(lambda x: x + 0.5, params)
    mgr.save_delta(15, newp)
    p2, _, step = quorum_restore(cfg, params, opt)
    assert step == 15
    assert trees_equal(newp, p2, atol=1e-6)


def test_delta_int8_error_feedback(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path), delta_int8=True)
    mgr = CheckpointManager(cfg)
    params, opt = tiny_state()
    mgr.save_baseline(0, params, opt)
    newp = jax.tree.map(lambda x: x + 0.01 * jnp.sign(x), params)
    mgr.save_delta(5, newp)
    p2, _, step = quorum_restore(cfg, params, opt)
    flat_a, flat_b = jax.tree.leaves(newp), jax.tree.leaves(p2)
    for a, b in zip(flat_a, flat_b):
        assert float(jnp.abs(a - b).max()) < 1e-3   # one-delta quant error


def test_quorum_survives_one_corrupt_replica(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path), replicas=3)
    mgr = CheckpointManager(cfg)
    params, opt = tiny_state()
    mgr.save_baseline(7, params, opt)
    corrupt_replica(cfg, replica=1)
    out = quorum_restore(cfg, params, opt)
    assert out is not None and out[2] == 7
    assert trees_equal(params, out[0])


def test_no_quorum_with_majority_corrupt(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path), replicas=3)
    mgr = CheckpointManager(cfg)
    params, opt = tiny_state()
    mgr.save_baseline(7, params, opt)
    corrupt_replica(cfg, 0)
    corrupt_replica(cfg, 1)
    assert quorum_restore(cfg, params, opt) is None


def test_journal_tail_and_torn_write(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path), replicas=3)
    mgr = CheckpointManager(cfg)
    for s in range(5):
        mgr.journal(s, {"loss": 1.0 / (s + 1)})
    # torn write on one replica
    p = tmp_path / "replica_0" / "journal.jsonl"
    p.write_text(p.read_text() + '{"step": 99, "los')
    tail = mgr.journal_tail()
    assert tail is not None and tail["step"] == 4


def test_atomic_write_never_leaves_partial(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path), replicas=1)
    mgr = CheckpointManager(cfg)
    params, opt = tiny_state()
    mgr.save_baseline(1, params, opt)
    files = list((tmp_path / "replica_0").glob("*.tmp.npz"))
    assert files == []


def test_reshard_roundtrip_single_device():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh((1,), ("data",))
    params, _ = tiny_state()
    pspecs = {"w": P("data", None), "layers": {"ln": P()}}
    placed = reshard(params, mesh, pspecs)
    assert trees_equal(params, placed)


def test_gc_keeps_latest_baselines(tmp_path):
    cfg = CkptConfig(directory=str(tmp_path), replicas=1, keep_baselines=2)
    mgr = CheckpointManager(cfg)
    params, opt = tiny_state()
    for s in (10, 20, 30):
        mgr.save_baseline(s, params, opt)
    names = sorted(f.name for f in (tmp_path / "replica_0").glob(
        "baseline_*.npz"))
    assert names == ["baseline_00000020.npz", "baseline_00000030.npz"]
