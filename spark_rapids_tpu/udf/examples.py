"""Example accelerated UDFs.

Reference: udf-examples/ ships URLDecode/URLEncode (RapidsUDF Scala UDFs)
plus native custom kernels (StringWordCount, CosineSimilarity) to show the
two acceleration tiers. The TPU-native versions demonstrate the same tiers:

- ``word_count``: a jax byte-matrix kernel — fuses into the surrounding
  whole-stage XLA program (the native-kernel tier, no JNI needed).
- ``pallas_axpy``: the same tier with an explicit Pallas kernel, showing how
  a hand-written TPU kernel slots into a columnar UDF (udf-examples'
  cosine_similarity.cu analogue; interpret mode keeps it runnable on CPU).
- ``url_decode`` / ``url_encode`` / ``cosine_similarity``: host columnar
  UDFs (vectorized numpy/stdlib) for shapes the device engine doesn't
  accelerate (dynamic-width strings, array columns) — the framework routes
  them through the host path with a recorded fallback reason, exactly like
  un-accelerated UDFs in the reference.
"""
from __future__ import annotations

import numpy as np

from ..columnar import dtypes as dt
from .columnar import columnar_udf

__all__ = ["url_decode", "url_encode", "word_count", "cosine_similarity",
           "pallas_axpy"]


# ---------------------------------------------------------------------------
# host tier: string/array UDFs
# ---------------------------------------------------------------------------
def _url_decode_host(vals):
    from urllib.parse import unquote_plus
    out = np.empty(len(vals), dtype=object)
    for i, v in enumerate(vals):
        out[i] = unquote_plus(v) if isinstance(v, str) else v
    return out


@columnar_udf(dt.STRING, name="url_decode", device_ok=False)
def url_decode(vals):
    """URL percent-decoding (udf-examples URLDecode analogue)."""
    return _url_decode_host(vals)


@columnar_udf(dt.STRING, name="url_encode", device_ok=False)
def url_encode(vals):
    """URL percent-encoding (udf-examples URLEncode analogue)."""
    from urllib.parse import quote_plus
    out = np.empty(len(vals), dtype=object)
    for i, v in enumerate(vals):
        out[i] = quote_plus(v) if isinstance(v, str) else v
    return out


@columnar_udf(dt.DOUBLE, name="cosine_similarity", device_ok=False)
def cosine_similarity(a, b):
    """Cosine similarity of two array<double> columns (udf-examples
    cosine_similarity native kernel analogue; arrays are host columns)."""
    out = np.empty(len(a), dtype=np.float64)
    for i in range(len(a)):
        x = np.asarray(a[i], dtype=np.float64)  # srtpu: sync-ok(host-side example UDF)
        y = np.asarray(b[i], dtype=np.float64)  # srtpu: sync-ok(host-side example UDF)
        denom = np.linalg.norm(x) * np.linalg.norm(y)
        out[i] = float(np.dot(x, y) / denom) if denom else float("nan")
    return out


# ---------------------------------------------------------------------------
# device tier: jax byte-matrix kernel
# ---------------------------------------------------------------------------
def _word_count_device(mat):
    # device strings are (rows, width) uint8 with zero padding; word count =
    # 1 + spaces (the empty string is recognized by its zero first byte)
    import jax.numpy as jnp
    if mat.ndim < 2 or mat.shape[1] == 0:
        return jnp.zeros(mat.shape[0], dtype=jnp.int32)
    spaces = jnp.sum(mat == np.uint8(32), axis=1)
    return jnp.where(mat[:, 0] == 0, 0, spaces + 1).astype(jnp.int32)


def _word_count_host(vals):
    out = np.zeros(len(vals), dtype=np.int32)
    for i, v in enumerate(vals):
        out[i] = (v.count(" ") + 1) if isinstance(v, str) and v else 0
    return out


@columnar_udf(dt.INT, name="word_count", host_fn=_word_count_host)
def word_count(mat):
    """Single-space-delimited word count (udf-examples StringWordCount
    native kernel analogue): on device one fused jnp reduction over the
    string byte matrix, on host a python split. Matches the native kernel's
    simple semantics (single spaces) — not Spark's split regex."""
    return _word_count_device(mat)


# ---------------------------------------------------------------------------
# device tier: explicit Pallas kernel
# ---------------------------------------------------------------------------
_LANES = 128
#: rows of one (rows, 128) float32 block: 512 KiB per operand, so the four
#: double-buffered operands take 4 MiB of a v5e core's 16 MiB scoped VMEM
_BLOCK_ROWS = 1024


def _axpy_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[...] * x_ref[...] + y_ref[...]


def _pallas_axpy_device(a, x, y):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    n = a.shape[0]
    # a column is viewed as (rows, 128) lanes, rows padded to the float32
    # tile's 8 sublanes, and walked by a 1-D grid of VMEM-sized blocks: a
    # whole 2^23-row SF1 column in one block is refused by the compiler
    # (RESOURCE_EXHAUSTED in vmem from 2^22 rows up)
    pad = (-n) % (8 * _LANES)

    def tiled(v):
        v = jnp.pad(jnp.asarray(v, dtype=jnp.float32), (0, pad))
        return v.reshape(-1, _LANES)

    a2, x2, y2 = tiled(a), tiled(x), tiled(y)
    rows = a2.shape[0]
    block = (min(rows, _BLOCK_ROWS), _LANES)
    # int32 on purpose: the package runs with x64 on, and Mosaic cannot
    # legalize the int64 a bare Python 0 would become in the index map
    spec = pl.BlockSpec(block, lambda i: (i, jnp.int32(0)))
    out = pl.pallas_call(
        _axpy_kernel,
        out_shape=jax.ShapeDtypeStruct(a2.shape, jnp.float32),
        grid=(pl.cdiv(rows, block[0]),),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        # compiled wherever there is an accelerator; the interpreter only
        # keeps the kernel runnable on the CPU test backend
        interpret=jax.default_backend() == "cpu",
    )(a2, x2, y2)
    return out.reshape(-1)[:n]


def _pallas_axpy_host(a, x, y):
    return (np.asarray(a, dtype=np.float32) * np.asarray(x, dtype=np.float32)  # srtpu: sync-ok(host-side example UDF)
            + np.asarray(y, dtype=np.float32))  # srtpu: sync-ok(host-side example UDF)


@columnar_udf(dt.FLOAT, name="pallas_axpy", host_fn=_pallas_axpy_host)
def pallas_axpy(a, x, y):
    """a*x + y as a hand-written Pallas TPU kernel wrapped in a columnar
    UDF — the pattern for plugging custom TPU kernels into queries."""
    return _pallas_axpy_device(a, x, y)
