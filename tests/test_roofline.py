"""HLO walker: exact FLOP accounting incl. while-loop trip counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import roofline


def _hlo(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_scan_trip_count_multiplied():
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    txt = _hlo(f, jnp.ones((128, 128), jnp.float32))
    c = roofline.analyze_hlo(txt)
    assert c.flops == pytest.approx(10 * 2 * 128 ** 3, rel=0.01)


def test_plain_matmul_flops():
    txt = _hlo(lambda a, b: a @ b,
               jnp.ones((64, 32), jnp.float32), jnp.ones((32, 16), jnp.float32))
    c = roofline.analyze_hlo(txt)
    assert c.flops == pytest.approx(2 * 64 * 32 * 16, rel=0.01)


def test_batched_dot_flops():
    f = lambda a, b: jnp.einsum("bij,bjk->bik", a, b)
    txt = _hlo(f, jnp.ones((4, 8, 16), jnp.float32), jnp.ones((4, 16, 8), jnp.float32))
    c = roofline.analyze_hlo(txt)
    assert c.flops == pytest.approx(2 * 4 * 8 * 16 * 8, rel=0.01)


def test_nested_scan_multiplies():
    def f(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    txt = _hlo(f, jnp.ones((64, 64), jnp.float32))
    c = roofline.analyze_hlo(txt)
    assert c.flops == pytest.approx(15 * 2 * 64 ** 3, rel=0.02)


def test_bytes_positive_and_sane():
    txt = _hlo(lambda a: (a @ a).sum(), jnp.ones((256, 256), jnp.float32))
    c = roofline.analyze_hlo(txt)
    assert c.bytes_accessed >= 2 * 256 * 256 * 4  # at least read a twice


def test_terms_and_bottleneck():
    cost = roofline.HloCost(
        flops=197e12, bytes_accessed=819e9 / 2, collective_bytes={}, n_collectives=0
    )
    t = roofline.roofline_terms(cost, roofline.peaks_for("TPU v5 lite"))
    assert t["bottleneck"] == "compute"
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(0.5)


def test_model_flops_moe_uses_active():
    from repro.configs import registry
    dense = registry.get("deepseek-67b")
    moe = registry.get("kimi-k2-1t-a32b")
    # kimi total params >> deepseek, but ACTIVE flops should be same order
    f_moe = roofline.model_flops(moe, 1000)
    f_dense = roofline.model_flops(dense, 1000)
    assert f_moe < 2 * f_dense  # ~32B active vs 67B dense


def test_shape_parse():
    b, e = roofline._shape_info("bf16[256,128]{1,0}")
    assert e == 256 * 128 and b == 2 * e
    b, e = roofline._shape_info("(s32[], f32[4,4]{1,0})")
    assert e == 1 + 16 and b == 4 + 64


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="no peak rates"):
        roofline.peaks_for("cpu")
