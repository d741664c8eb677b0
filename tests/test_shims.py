"""Version shim layer tests (reference: ShimLoader.scala per-version shim
resolution)."""
import numpy as np

import spark_rapids_tpu.shims as shims
from spark_rapids_tpu.shims import (HostLibShims, ShimVersions,
                                    detect_versions, get_shims,
                                    select_provider)


def _v(pandas=(2, 2), numpy=(1, 26), pyarrow=(15, 0), jax=(0, 4, 30)):
    return ShimVersions(pandas, numpy, pyarrow, jax)


def test_detect_and_active_shims():
    versions = detect_versions()
    assert len(versions.pandas) >= 2 and len(versions.jax) >= 2
    active = get_shims()
    assert isinstance(active, HostLibShims)
    # probed once: same instance on re-query (ShimLoader caching)
    assert get_shims() is active


def test_provider_selection_by_version():
    # one installation is supported: no legacy provider is shipped, so
    # every version resolves to the current-API provider
    for versions in (_v(), _v(pandas=(1, 4)), _v(jax=(0, 4, 20))):
        assert select_provider(versions) is HostLibShims


def test_shim_methods_functional():
    s = get_shims()
    codes, uniques = s.factorize(np.array(["b", "a", "b"], dtype=object))
    assert codes.tolist() == [0, 1, 0]
    uniq, first, inv = s.unique_rows(np.array([[1, 2], [3, 4], [1, 2]]))
    assert inv.ndim == 1 and inv.tolist() == [0, 1, 0]
    assert not s.is_tracer(np.int32(3))
    import jax
    traced = {"seen": None}

    def probe(x):
        traced["seen"] = s.is_tracer(x)
        return x

    jax.jit(probe)(np.float32(1.0))
    assert traced["seen"] is True
    assert s.tree_map(lambda a, b: a + b, {"x": 1}, {"x": 2}) == {"x": 3}


def test_register_custom_provider():
    class QuirkShims(HostLibShims):
        shim_name = "quirk"

    shims.register_shim_provider(lambda v: v.pyarrow >= (999,), QuirkShims)
    try:
        assert select_provider(_v(pyarrow=(999, 1))) is QuirkShims
        assert select_provider(_v()) is HostLibShims
    finally:
        shims._PROVIDERS.pop(0)
        shims._ACTIVE = None
