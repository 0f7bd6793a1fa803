"""Checkpoints: atomic roundtrip, async, GC, resume, restore-with-sharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt


def tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nest": {"b": jnp.ones((4,), jnp.int32)},
            "state": {"step": jnp.int32(7)}}


def test_roundtrip(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 3, tree())
    restored, step = ckpt.restore(d, tree())
    assert step == 3
    assert np.array_equal(restored["a"], tree()["a"])
    assert np.array_equal(restored["nest"]["b"], tree()["nest"]["b"])


def test_async_and_latest(tmp_path):
    d = str(tmp_path)
    th = ckpt.save_async(d, 1, tree())
    th.join()
    ckpt.save(d, 5, tree())
    assert ckpt.latest_step(d) == 5


def test_gc_keeps_last(tmp_path):
    d = str(tmp_path)
    for s in range(6):
        ckpt.save(d, s, tree(), keep=2)
    steps = sorted(int(n[5:]) for n in os.listdir(d) if n.startswith("step_"))
    assert steps == [4, 5]


def test_uncommitted_ignored(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 2, tree())
    # fake a torn save
    os.makedirs(os.path.join(d, "step_00000009"))
    assert ckpt.latest_step(d) == 2


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path), tree())


def test_elastic_restore_with_shardings(tmp_path):
    """Restore onto explicit (single-device) shardings — the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    d = str(tmp_path)
    ckpt.save(d, 1, tree())
    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree())
    restored, _ = ckpt.restore(d, tree(), shardings=sh)
    assert restored["a"].sharding == NamedSharding(mesh, P())
    assert np.array_equal(restored["a"], tree()["a"])


def test_dtype_cast_on_restore(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, {"w": jnp.ones((2,), jnp.float32)})
    template = {"w": jnp.zeros((2,), jnp.bfloat16)}
    restored, _ = ckpt.restore(d, template)
    assert restored["w"].dtype == jnp.bfloat16
