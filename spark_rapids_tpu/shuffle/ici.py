"""ICI mesh-collective exchange — the accelerated shuffle tier.

Reference mapping (SURVEY §2.7 / §5): the UCX RDMA transport
(shuffle-plugin/.../UCX.scala:69) moves partitioned batches executor-to-
executor over NVLink/IB. The TPU-native equivalent keeps exchanges ON DEVICE:
rows live as one mesh-sharded DeviceTable; a hash-partition kernel + a single
``jax.lax.all_to_all`` over the ``dp`` axis re-homes every row across ICI
links inside one XLA program — no host staging, no serialization.

Static-shape contract: all_to_all needs equal per-destination quotas. The
caller may pass ``quota`` (slots per source-destination pair, from a prior
count pass — exec/exchange.py does this) to right-size the intermediate;
without it each shard reserves ``local_capacity`` slots per destination
(worst case, an n_devices× blowup kept only as the safe default).

Works under ``shard_map`` on any mesh — real ICI on TPU pods, XLA-emulated on
the CPU test mesh (tests/conftest.py).
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..columnar.device import (DeviceColumn, DeviceTable,
                               stable_counting_order)
from ..utils import movement
from ..utils.compile_cache import aot_program, named_jit
from ..utils.tracing import get_tracer
from . import telemetry
from .manager import device_partition_ids

__all__ = ["ici_all_to_all_exchange", "exchange_program", "shard_table",
           "unshard_table"]

# movement-observatory site identity (utils/movement.py SITES)
_MOVE_UNSHARD = "spark_rapids_tpu/shuffle/ici.py::unshard_table"


def shard_table(table: DeviceTable, mesh: Mesh, axis: str = "dp"
                ) -> DeviceTable:
    """Place a DeviceTable row-sharded over the mesh axis."""
    n = mesh.shape[axis]
    assert table.capacity % n == 0, \
        f"capacity {table.capacity} not divisible by mesh axis {n}"
    sharding = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())

    cols = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), table.columns)
    return DeviceTable(cols,
                       jax.device_put(table.row_mask, sharding),
                       jax.device_put(table.num_rows, rep), table.names)


def unshard_table(table: DeviceTable) -> DeviceTable:
    # ONE bulk device_get of the whole (columns, mask) leaf pytree — the
    # PR-18 funnel shape — instead of one blocking np.asarray round trip
    # per column plane; the ledger sees a single D2H crossing
    t0 = movement.clock()
    host_cols, host_mask = jax.device_get(  # srtpu: sync-ok(deliberate unshard gather: one bulk host materialization at the shuffle boundary)
        (table.columns, table.row_mask))
    movement.note_d2h(
        _MOVE_UNSHARD,
        lambda: sum(a.nbytes for a in
                    jax.tree_util.tree_leaves((host_cols, host_mask))),
        t0)
    cols = jax.tree_util.tree_map(jnp.asarray, host_cols)
    mask = jnp.asarray(host_mask)
    return DeviceTable(cols, mask, jnp.sum(mask, dtype=jnp.int32), table.names)


def _program_key(table: DeviceTable, key_names: List[str], mesh: Mesh,
                 axis: str, quota: int | None) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(table.columns)
    return (tuple(str(d) for d in mesh.devices.flat), axis, quota,
            tuple(table.names), tuple(key_names), str(treedef),
            tuple((l.shape, str(l.dtype)) for l in leaves),
            (table.row_mask.shape, str(table.row_mask.dtype)))


def exchange_program(columns, names, key_names: List[str], mesh: Mesh,
                     axis: str = "dp", quota: int | None = None):
    """The jitted shard_map all-to-all program for tables shaped like
    ``columns`` (arrays or ShapeDtypeStructs — only the pytree structure
    is read), taking ``(columns, row_mask)`` row-sharded over ``axis``."""
    n = mesh.shape[axis]

    # the column tuple is a pytree whose leaves are the per-column planes
    # (data/validity/lengths/elem_validity + struct children, recursively)
    # — tree_map applies the scatter + all_to_all to every plane uniformly
    def local(columns, mask):
        cap = mask.shape[0]
        q = cap if quota is None else min(quota, cap)
        local_tbl = DeviceTable(columns, mask,
                                jnp.sum(mask, dtype=jnp.int32), names)
        pid = device_partition_ids(local_tbl, key_names, n)
        pid = jnp.where(mask, pid, n)  # park inactive rows past the end
        order = stable_counting_order(pid, n + 1)
        sorted_pid = jnp.take(pid, order)
        iota = jnp.arange(cap, dtype=jnp.int32)
        start = jnp.searchsorted(sorted_pid,
                                 jnp.arange(n, dtype=sorted_pid.dtype))
        dst = jnp.clip(sorted_pid, 0, n - 1).astype(jnp.int32)
        k = iota - jnp.take(start, dst).astype(jnp.int32)
        ok = sorted_pid < n

        def xform(x):
            xs = jnp.take(x, order, axis=0)
            buckets = jnp.zeros((n, q) + xs.shape[1:], dtype=xs.dtype)
            fill = jnp.where(ok.reshape((-1,) + (1,) * (xs.ndim - 1)), xs,
                             jnp.zeros_like(xs))
            scattered = buckets.at[dst, k].set(fill, mode="drop")
            return jax.lax.all_to_all(scattered, axis, 0, 0, tiled=True) \
                .reshape((n * q,) + x.shape[1:])

        slot_mask = jnp.zeros((n, q), dtype=bool).at[dst, k].set(
            ok, mode="drop")
        out_mask = jax.lax.all_to_all(slot_mask, axis, 0, 0,
                                      tiled=True).reshape(n * q)
        out_cols = jax.tree_util.tree_map(xform, columns)
        return out_cols, out_mask

    col_specs = jax.tree_util.tree_map(lambda _: P(axis), columns)
    # check_vma off: the exchange's output specs are data-dependent in
    # ways the static replication checker rejects
    return named_jit(jax.shard_map(local, mesh=mesh,
                                   in_specs=(col_specs, P(axis)),
                                   out_specs=(col_specs, P(axis)),
                                   check_vma=False), "ici_all_to_all")


_PROGRAM = "srt_ici_all_to_all"


def _crossing_bytes(table: DeviceTable, n: int, quota: int | None) -> int:
    """Bytes the all-to-all hands over: every shard sends ``quota`` slots of
    every plane (and of the mask) to each of the ``n`` shards, padding
    included; the 1/n of them addressed to the sending shard stay on it."""
    cap = table.capacity // n
    q = cap if quota is None else min(quota, cap)
    leaves = jax.tree_util.tree_leaves((table.columns, table.row_mask))
    return sum(l.nbytes // l.shape[0] for l in leaves) * n * n * q


def ici_all_to_all_exchange(table: DeviceTable, key_names: List[str],
                            mesh: Mesh, axis: str = "dp",
                            quota: int | None = None,
                            telemetry_sid: int | None = None
                            ) -> DeviceTable:
    """Hash-exchange a row-sharded table so rows with equal keys land on the
    same shard, as one jitted shard_map program (collectives over ICI).

    ``quota`` is the per-(source, destination) slot count; it MUST be >= the
    max rows any shard sends to any destination (callers size it from a count
    pass; undersizing would drop rows). Defaults to local capacity (always
    safe). Returns a row-sharded table with per-shard capacity n * quota
    (padding masked off)."""
    n = mesh.shape[axis]
    names = table.names
    tracer = get_tracer()
    # the one-time lower + XLA compile is its own observatory phase:
    # folded into ``dispatch`` a cold cache would read as shuffle wall and
    # trip the sentinel's shuffle-wall gate
    t0 = telemetry.clock()
    prog, compiled = aot_program(
        _program_key(table, key_names, mesh, axis, quota),
        lambda: exchange_program(table.columns, names, key_names, mesh,
                                 axis, quota),
        (table.columns, table.row_mask), name="ici_all_to_all")
    if compiled:
        telemetry.note_transfer("ici", "compile", shuffle_id=telemetry_sid,
                                t0=t0, queue_depth=n)
    # collective dispatch wall: dispatch of the all-to-all over n devices
    # (compile is its own phase above); wire bytes are the padded sharded
    # input actually crossing ICI links (vs the pre-padding logical bytes
    # the exchange exec notes at enqueue)
    t0 = telemetry.clock()
    with tracer.span("dispatch", "dispatch", on=table.row_mask,
                     program=_PROGRAM,
                     bytes=_crossing_bytes(table, n, quota)):
        out_cols, mask = prog(table.columns, table.row_mask)
        # the eager sum over the sharded mask is this step's too: its
        # host dispatch is booked here, not to the ``stage`` around it
        total = jnp.sum(mask, dtype=jnp.int32)
    telemetry.note_transfer("ici", "dispatch", shuffle_id=telemetry_sid,
                            t0=t0, queue_depth=n,
                            wire_bytes=lambda: table.nbytes())
    return DeviceTable(tuple(out_cols), mask, total, names)
