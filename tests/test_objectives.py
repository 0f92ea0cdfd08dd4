"""BBOB objective sanity + search-space tests."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.bo.objectives import OBJECTIVES, make_objective
from repro.bo.space import BoxSpace


@pytest.mark.parametrize("name", [o for o in OBJECTIVES
                                  if o != "rosenbrock"])
@pytest.mark.parametrize("dim", [2, 5, 10])
def test_optimum_value(name, dim):
    f = make_objective(name, dim, seed=3)
    v_opt = f(f.x_opt)
    assert v_opt <= 1e-9, (name, v_opt)
    rng = np.random.default_rng(0)
    for _ in range(16):
        x = rng.uniform(-5, 5, dim)
        assert f(x) >= v_opt - 1e-12


def test_rosenbrock_optimum():
    f = make_objective("rosenbrock", 5)
    assert f(np.ones(5)) == 0.0


def test_instances_differ_by_seed():
    f1 = make_objective("rastrigin", 4, seed=1)
    f2 = make_objective("rastrigin", 4, seed=2)
    assert not np.allclose(f1.x_opt, f2.x_opt)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000))
def test_property_space_roundtrip(seed):
    rng = np.random.default_rng(seed)
    lo = rng.uniform(-10, 0, 4)
    hi = lo + rng.uniform(0.5, 10, 4)
    sp = BoxSpace(lo, hi)
    x = sp.sample(rng, 8)
    u = sp.to_unit(x)
    assert np.all(u >= -1e-12) and np.all(u <= 1 + 1e-12)
    np.testing.assert_allclose(sp.from_unit(u), x, atol=1e-10)


def test_space_validation():
    with pytest.raises(ValueError):
        BoxSpace(np.array([0.0, 1.0]), np.array([1.0, 1.0]))
