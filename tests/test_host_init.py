"""FLAGS_host_init: host-side (numpy) parameter initialization.

Sampling on the host removes every per-parameter device program from
model build. Must keep: seed determinism, target dtype, the documented
distributions.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.nn import initializer as I


@pytest.fixture(autouse=True)
def _host_init_flag():
    paddle.set_flags({"host_init": True})
    yield
    paddle.set_flags({"host_init": False})


def test_same_seed_same_params():
    paddle.seed(1234)
    l1 = nn.Linear(32, 48)
    paddle.seed(1234)
    l2 = nn.Linear(32, 48)
    np.testing.assert_array_equal(np.asarray(l1.weight._value),
                                  np.asarray(l2.weight._value))
    np.testing.assert_array_equal(np.asarray(l1.bias._value),
                                  np.asarray(l2.bias._value))


def test_different_draws_differ():
    paddle.seed(7)
    a = I.Normal(0, 1)((64,), "float32")
    b = I.Normal(0, 1)((64,), "float32")
    assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("init", [
    I.Normal(0, 1), I.TruncatedNormal(), I.Uniform(-1, 1),
    I.XavierNormal(), I.XavierUniform(), I.KaimingNormal(),
    I.KaimingUniform(), I.Orthogonal(), I.Constant(3.0),
])
def test_dtype_respected(init):
    paddle.seed(0)
    v32 = init((16, 16), "float32")
    assert str(np.asarray(v32).dtype) == "float32"
    vb = init((16, 16), paddle.bfloat16)
    assert "bfloat16" in str(vb.dtype)


def test_distributions():
    paddle.seed(0)
    n = np.asarray(I.Normal(2.0, 0.5)((20000,), "float32"))
    assert abs(n.mean() - 2.0) < 0.02 and abs(n.std() - 0.5) < 0.02
    u = np.asarray(I.Uniform(-3, 1)((20000,), "float32"))
    assert u.min() >= -3 and u.max() <= 1 and abs(u.mean() + 1.0) < 0.05
    t = np.asarray(I.TruncatedNormal()((20000,), "float32"))
    assert t.min() >= -2.001 and t.max() <= 2.001
    q = np.asarray(I.Orthogonal()((32, 32), "float32"))
    np.testing.assert_allclose(q @ q.T, np.eye(32), atol=1e-4)


def test_jax_path_unaffected():
    paddle.set_flags({"host_init": False})
    paddle.seed(42)
    l1 = nn.Linear(8, 8)
    paddle.seed(42)
    l2 = nn.Linear(8, 8)
    np.testing.assert_array_equal(np.asarray(l1.weight._value),
                                  np.asarray(l2.weight._value))


def test_trainable_model_from_host_init():
    """A model built under host_init trains exactly like any other."""
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8).astype("float32"))
    y = m(x).mean()
    y.backward()
    g = m[0].weight.grad
    assert g is not None and np.isfinite(np.asarray(g._value)).all()
