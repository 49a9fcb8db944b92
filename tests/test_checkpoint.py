"""Checkpoint substrate: atomicity, integrity, retention, elasticity."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import checkpoint as ck


def _tree(v=1.0):
    return {"a": jnp.full((4, 4), v), "b": {"c": jnp.arange(6).reshape(2, 3)}}


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path)
    ck.save(d, 5, _tree(2.5), extra={"step": 5})
    out, extra = ck.restore(d, _tree(0.0))
    np.testing.assert_array_equal(np.asarray(out["a"]), 2.5)
    assert extra["step"] == 5


def test_retention(tmp_path):
    d = str(tmp_path)
    for s in [1, 2, 3, 4, 5]:
        ck.save(d, s, _tree(), keep=2)
    steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    assert len(steps) == 2 and ck.latest_step(d) == 5


def test_corruption_detected(tmp_path):
    d = str(tmp_path)
    path = ck.save(d, 1, _tree())
    # rewrite the arrays file with silently-changed data (manifest CRCs stale)
    f = os.path.join(path, "arrays.npz")
    loaded = dict(np.load(f))
    loaded["a"] = loaded["a"] + 1.0
    with open(f, "wb") as fh:
        np.savez(fh, **loaded)
    with pytest.raises(ck.CheckpointError, match="CRC"):
        ck.restore(d, _tree())


def test_stale_tmp_cleaned(tmp_path):
    d = str(tmp_path)
    os.makedirs(os.path.join(d, "step_00000001.tmp"))
    ck.save(d, 2, _tree())
    assert not any(x.endswith(".tmp") for x in os.listdir(d))


def test_shape_mismatch_rejected(tmp_path):
    d = str(tmp_path)
    ck.save(d, 1, _tree())
    bad = {"a": jnp.zeros((2, 2)), "b": {"c": jnp.zeros((2, 3), jnp.int32)}}
    with pytest.raises(ck.CheckpointError):
        ck.restore(d, bad)


def test_elastic_restore_on_new_sharding(tmp_path):
    """Checkpoint written on one 'mesh' restores under different shardings
    (here: simply new device placement — layout is logical)."""
    d = str(tmp_path)
    ck.save(d, 1, _tree(3.0))
    import jax

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        _tree(),
    )
    out, _ = ck.restore(d, _tree(), shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["a"]), 3.0)
