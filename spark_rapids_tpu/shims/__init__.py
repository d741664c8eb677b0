"""Version shim layer.

Reference: the plugin compiles against 14+ Spark versions through per-version
shim classes resolved at runtime by ShimLoader
(sql-plugin/src/main/spark3*/...; ShimLoader.scala getShimVersion) so one
artifact runs everywhere. This framework's host engine sits on pyarrow /
pandas / numpy / jax instead of Spark, and THOSE APIs drift across versions
the same way:

- pandas renamed ``factorize(na_sentinel=...)`` to ``use_na_sentinel``
  (1.5) and removed the old name (2.0),
- numpy 2.0 changed ``np.unique(return_inverse=True)``'s inverse shape for
  multi-dimensional input,
- jax moved ``jax.tree_map`` to ``jax.tree_util.tree_map`` (0.4.26 removal)
  and is migrating ``jax.core`` internals (Tracer) to ``jax.extend``.

Same design as the reference: a provider class per version range, a loader
that probes installed versions once and composes the active shim set, and
call sites that go through ``get_shims()`` instead of the raw APIs. One
installation is supported (pandas 2, numpy 2, jax 0.9), so the current-API
provider is the only one shipped; ``register_shim_provider`` is where a
version quirk goes when one appears.
"""
from __future__ import annotations

from typing import Callable, List, Tuple, Type

__all__ = ["ShimVersions", "HostLibShims", "get_shims", "detect_versions",
           "register_shim_provider"]


def _parse(v: str) -> Tuple[int, ...]:
    parts = []
    for tok in v.split("."):
        digits = "".join(ch for ch in tok if ch.isdigit())
        if not digits:
            break
        parts.append(int(digits))
    return tuple(parts) or (0,)


class ShimVersions:
    """Installed host-library versions (the SparkShimVersion analogue)."""

    def __init__(self, pandas: Tuple[int, ...], numpy: Tuple[int, ...],
                 pyarrow: Tuple[int, ...], jax: Tuple[int, ...]):
        self.pandas = pandas
        self.numpy = numpy
        self.pyarrow = pyarrow
        self.jax = jax

    def __repr__(self):
        def s(t):
            return ".".join(map(str, t))
        return (f"ShimVersions(pandas={s(self.pandas)}, numpy={s(self.numpy)}, "
                f"pyarrow={s(self.pyarrow)}, jax={s(self.jax)})")


def detect_versions() -> ShimVersions:
    import jax
    import numpy
    import pandas
    import pyarrow
    return ShimVersions(_parse(pandas.__version__), _parse(numpy.__version__),
                        _parse(pyarrow.__version__), _parse(jax.__version__))


# ---------------------------------------------------------------------------
# Providers
# ---------------------------------------------------------------------------
class HostLibShims:
    """Current-API provider (latest pandas/numpy/jax)."""

    shim_name = "current"

    def __init__(self, versions: ShimVersions):
        self.versions = versions

    # -- pandas ---------------------------------------------------------------
    def factorize(self, values, sort: bool = False):
        """factorize with nulls coded (never -1-sentineled away)."""
        import pandas as pd
        return pd.factorize(values, use_na_sentinel=False, sort=sort)

    # -- numpy ----------------------------------------------------------------
    def unique_rows(self, mat):
        """np.unique(axis=0) with a FLAT inverse regardless of numpy major
        (numpy 2.0 returns an inverse shaped like the input rows)."""
        import numpy as np
        uniq, first, inv = np.unique(mat, axis=0, return_index=True,
                                     return_inverse=True)
        return uniq, first, inv.reshape(-1)

    # -- jax ------------------------------------------------------------------
    def is_tracer(self, x) -> bool:
        import jax
        return isinstance(x, jax.core.Tracer)

    def tree_map(self, fn, *trees):
        from jax import tree_util
        return tree_util.tree_map(fn, *trees)


# (predicate, provider) — FIRST match wins, mirroring the reference's
# per-version shim resolution; extend with register_shim_provider.
_PROVIDERS: List[Tuple[Callable[[ShimVersions], bool], Type[HostLibShims]]] = [
    (lambda v: True, HostLibShims),
]


def register_shim_provider(predicate: Callable[[ShimVersions], bool],
                           provider: Type[HostLibShims]) -> None:
    """Prepend a custom provider (tests / downstream version quirks)."""
    _PROVIDERS.insert(0, (predicate, provider))
    global _ACTIVE
    _ACTIVE = None


def select_provider(versions: ShimVersions) -> Type[HostLibShims]:
    for pred, cls in _PROVIDERS:
        if pred(versions):
            return cls
    return HostLibShims


_ACTIVE: "HostLibShims | None" = None


def get_shims() -> HostLibShims:
    """The active shim set (probed once per process, like ShimLoader)."""
    global _ACTIVE
    if _ACTIVE is None:
        versions = detect_versions()
        _ACTIVE = select_provider(versions)(versions)
    return _ACTIVE
