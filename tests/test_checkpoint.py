"""Checkpoint/restore: roundtrip, async writer, GC, resharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer


def _tree():
    return {"w": jnp.arange(24.0).reshape(4, 6),
            "opt": {"m": jnp.ones((3,), jnp.float32),
                    "step": jnp.int32(7)}}


def test_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = _tree()
    ck.save(5, tree, extra={"loss": 1.25})
    restored, extra = ck.restore(jax.eval_shape(lambda: tree))
    assert extra["loss"] == 1.25
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_writer_and_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in [1, 2, 3, 4]:
        ck.save(s, _tree())
    ck.wait()
    assert ck.all_steps() == [3, 4]


def test_restore_specific_step(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=5)
    tree = _tree()
    ck.save(1, tree)
    ck.save(2, jax.tree.map(lambda x: x * 2, tree))
    ck.wait()
    r1, _ = ck.restore(jax.eval_shape(lambda: tree), step=1)
    r2, _ = ck.restore(jax.eval_shape(lambda: tree), step=2)
    assert float(r2["w"][0, 1]) == 2 * float(r1["w"][0, 1])


def test_incomplete_checkpoint_ignored(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _tree())
    ck.wait()
    # simulate a crash mid-write: directory without the _COMPLETE flag
    os.makedirs(tmp_path / "step_0000000099")
    assert ck.all_steps() == [1]
    assert ck.latest_step() == 1


def test_shape_mismatch_rejected(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _tree())
    ck.wait()
    bad = {"w": jnp.zeros((2, 2)), "opt": {"m": jnp.ones((3,)),
                                           "step": jnp.int32(0)}}
    with pytest.raises(ValueError):
        ck.restore(jax.eval_shape(lambda: bad))


def test_elastic_restore_mesh_change(tmp_path):
    """Restore under a different mesh/shardings (elastic restart)."""
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.runtime.elastic import elastic_restore
    ck = Checkpointer(str(tmp_path))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ck.save(1, tree)
    ck.wait()
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    restored, _ = elastic_restore(ck, jax.eval_shape(lambda: tree), mesh,
                                  lambda key, leaf: P())
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
