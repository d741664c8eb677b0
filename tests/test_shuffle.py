"""Shuffle subsystem tests (reference analogues: RapidsShuffleClientSuite /
ServerSuite driving protocol state machines with mock transports,
RapidsShuffleTestHelper — SURVEY §4.2)."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import DeviceTable, HostTable
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.shuffle.manager import (HeartbeatManager, ShuffleManager,
                                              device_partition_ids)
from spark_rapids_tpu.shuffle.serializer import (deserialize_table,
                                                 serialize_table)
from spark_rapids_tpu.shuffle.transport import (BlockId, LocalShuffleTransport,
                                                ShuffleTransport,
                                                load_transport)


def _host_table(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return HostTable.from_arrow(pa.table({
        "k": pa.array(rng.integers(0, 10, n)),
        "v": pa.array(rng.uniform(0, 1, n)),
        "s": pa.array([f"s{i % 7}" if i % 11 else None for i in range(n)]),
    }))


def test_serializer_roundtrip():
    t = _host_table()
    for codec in ("none", "zlib"):
        data = serialize_table(t, codec)
        back = deserialize_table(data)
        assert back.to_arrow().equals(t.to_arrow())


def test_serializer_empty_and_nulls():
    t = HostTable.from_arrow(pa.table({
        "a": pa.array([], type=pa.int64()),
        "s": pa.array([], type=pa.string())}))
    assert deserialize_table(serialize_table(t)).to_arrow().equals(t.to_arrow())
    t2 = HostTable.from_arrow(pa.table({
        "a": pa.array([None, None], type=pa.int64())}))
    assert deserialize_table(serialize_table(t2)).to_arrow().equals(t2.to_arrow())


def test_serializer_nested_types_roundtrip():
    """Nested columns ship as embedded Arrow IPC (offsets + child buffers —
    JCudfSerialization nested layout analogue), so collect_list/set partial
    states survive a real cross-process shuffle."""
    t = HostTable.from_arrow(pa.table({
        "k": pa.array([1, 2, 3, 4], type=pa.int64()),
        "arr": pa.array([[1, 2], [], None, [5, None, 7]],
                        type=pa.list_(pa.int64())),
        "st": pa.array([{"a": 1, "b": "x"}, {"a": 2, "b": None},
                        None, {"a": 4, "b": "w"}],
                       type=pa.struct([("a", pa.int64()), ("b", pa.string())])),
        "m": pa.array([[("k1", 1.5)], [], None, [("k2", 2.5), ("k3", 3.5)]],
                      type=pa.map_(pa.string(), pa.float64())),
    }))
    for codec in ("none", "zlib"):
        back = deserialize_table(serialize_table(t, codec))
        assert back.column("arr").values.tolist()[0] == [1, 2]
        assert back.to_arrow().equals(t.to_arrow()), codec


def test_serializer_nested_deep():
    t = HostTable.from_arrow(pa.table({
        "nested": pa.array([[[1], [2, 3]], None, [[4]]],
                           type=pa.list_(pa.list_(pa.int64()))),
    }))
    back = deserialize_table(serialize_table(t))
    assert back.to_arrow().equals(t.to_arrow())


def test_transport_reflective_load():
    conf = RapidsConf()
    tr = load_transport(conf)
    assert isinstance(tr, LocalShuffleTransport)


class MockFlakyTransport(ShuffleTransport):
    """Returns blocks out of order and drops nothing (protocol mock)."""

    def __init__(self, conf=None):
        self.inner = LocalShuffleTransport()
        self.fetch_calls = 0

    def publish(self, block, payload):
        self.inner.publish(block, payload)

    def fetch(self, blocks):
        self.fetch_calls += 1
        yield from self.inner.fetch(list(reversed(blocks)))

    def remove_shuffle(self, sid):
        self.inner.remove_shuffle(sid)


def test_manager_write_read_roundtrip():
    mgr = ShuffleManager(transport=MockFlakyTransport())
    nparts = 4
    t = _host_table(200, seed=1)
    dt_ = DeviceTable.from_host(t, min_bucket=8)
    sid = mgr.new_shuffle_id()
    sizes = mgr.write_partition(sid, map_id=0, batches=iter([dt_]),
                                key_names=["k"], num_parts=nparts)
    assert sum(1 for s in sizes if s > 0) >= 2
    rows = 0
    seen_keys = {}
    for p in range(nparts):
        for batch in mgr.read_partition(sid, num_maps=1, reduce_id=p,
                                        min_bucket=8):
            ht = batch.to_host()
            rows += ht.num_rows
            for kv in ht.column("k").values:
                seen_keys.setdefault(int(kv), set()).add(p)
    assert rows == 200
    # every key lands in exactly one partition
    assert all(len(parts) == 1 for parts in seen_keys.values())


def _float_keys(dtype, n=4096, seed=3):
    """Cent values up to 560,000.00 (``o_totalprice``'s domain), random bit
    patterns, and the edge values: subnormals, +-inf, -0.0 / +0.0 and NaNs
    with other payloads; a few nulls."""
    rng = np.random.default_rng(seed)
    width = np.dtype(dtype).itemsize * 8
    bits = rng.integers(0, 2**width - 1, n // 2, dtype=f"u{width // 8}")
    edge = np.array([0.0, -0.0, np.inf, -np.inf, 5e-324, -1e-310, 1e-45,
                     1.4e-45, 3e-39, 1e-35, 1e-22, 1e38, 3.5e38, 1e300])
    nans = np.array([0x7FF8000000000000, 0xFFF8000000000001,
                     0x7FF0000000000123], np.uint64).view(np.float64)
    with np.errstate(over="ignore", invalid="ignore"):
        vals = np.concatenate([
            rng.integers(0, 56_000_000, n // 2) / 100.0, edge, nans
        ]).astype(dtype)
    vals = np.concatenate([vals, bits.view(dtype)])
    if dtype == np.float32:     # NaNs of other float32 payloads
        vals = np.concatenate([vals, np.array(
            [0x7FC00000, 0xFFC00001, 0x7F800123], np.uint32).view(
                np.float32)])
    valid = np.ones(len(vals), bool)
    valid[rng.integers(0, len(vals), 16)] = False
    return pa.array(vals, mask=~valid)


def _keyed_table(dtype):
    f = _float_keys(dtype)
    n = len(f)
    rng = np.random.default_rng(4)
    return HostTable.from_arrow(pa.table({
        "f": f, "k": pa.array(rng.integers(-2**40, 2**40, n)),
        "d": pa.array(rng.integers(8000, 10600, n).astype(np.int32),
                      pa.int32()).cast(pa.date32())}))


@pytest.mark.parametrize("case,keys", [
    ("int", ["k"]),
    ("float64", ["f"]), ("float32", ["f"]),
    ("float64+int+date", ["d", "f", "k"]),
    ("float32+int+date", ["k", "f", "d"]),
])
def test_device_partitioner_matches_host(case, keys):
    """Fixed-width keys land on the same partition in both engines, bit for
    bit, float keys too (their hash goes through float32 words the TPU can
    bitcast: ``expr/hashing.py`` ``float_key_bits``)."""
    from spark_rapids_tpu.plan.physical import murmur_hash_columns
    if case == "int":
        t = _host_table(128, seed=2)
    else:
        t = _keyed_table(np.float32 if "32" in case else np.float64)
    dt_ = DeviceTable.from_host(t, min_bucket=8)
    for n in (4, 8):
        dev = np.asarray(device_partition_ids(dt_, keys, n))[:t.num_rows]
        host = (murmur_hash_columns(t, keys) % np.uint32(n)).astype(
            np.int32)
        np.testing.assert_array_equal(dev, host)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("engine", ["device", "host"])
def test_equal_float_keys_share_a_partition(dtype, engine):
    """By the group-by's equality: -0.0 with +0.0, every NaN with every
    other whatever its sign and payload."""
    from spark_rapids_tpu.plan.physical import murmur_hash_columns
    nans = np.array([0x7FF8000000000000, 0xFFF8000000000001,
                     0x7FF0000000000123], np.uint64).view(np.float64)
    with np.errstate(invalid="ignore"):
        vals = np.concatenate([[0.0, -0.0], nans]).astype(dtype)
    t = HostTable.from_arrow(pa.table({"f": vals}))
    for n in (2, 4, 8, 64):
        if engine == "device":
            got = np.asarray(device_partition_ids(
                DeviceTable.from_host(t, min_bucket=8), ["f"], n))[:5]
        else:
            got = murmur_hash_columns(t, ["f"]) % np.uint32(n)
        assert got[0] == got[1] and got[2] == got[3] == got[4], (n, got)


#: partition ids the parent of PR 38 gave integer keys: the change touched
#: the float branch only, so an integer-keyed exchange (every exchange of
#: ``sf1-mesh4.q3``) moves the same rows to the same devices as before
_INT_KEYS = [0, 1, -1, 7, 2**40 + 3, 1_500_000, 5_999_997, -2**63,
             2**63 - 1, 123456789]
_INT32_KEYS = [8035, 10591, 0, -1, 9000, 9131, 8400, 10000, 2**31 - 1,
               -2**31]


@pytest.mark.parametrize("keys,n,want", [
    (["k"], 4, [2, 1, 2, 2, 2, 1, 1, 2, 2, 0]),
    (["k"], 8, [6, 5, 6, 2, 2, 1, 5, 6, 6, 4]),
    (["d"], 4, [2, 1, 2, 2, 2, 0, 3, 0, 2, 2]),
    (["d"], 8, [2, 5, 6, 6, 2, 0, 7, 0, 6, 6]),
    (["k", "d"], 4, [2, 2, 2, 2, 2, 3, 0, 0, 2, 0]),
    (["k", "d"], 8, [6, 6, 2, 6, 2, 7, 4, 4, 2, 0]),
])
def test_integer_keys_keep_the_parents_placement(keys, n, want):
    from spark_rapids_tpu.plan.physical import murmur_hash_columns
    t = HostTable.from_arrow(pa.table({
        "k": pa.array(_INT_KEYS, pa.int64()),
        "d": pa.array(_INT32_KEYS, pa.int32())}))
    dev = device_partition_ids(DeviceTable.from_host(t, min_bucket=8),
                               keys, n)
    assert np.asarray(dev)[:len(want)].tolist() == want
    assert (murmur_hash_columns(t, keys) % np.uint32(n)).tolist() == want


@pytest.fixture(scope="module")
def float_key_frame():
    """A float key holding both zeros and NaNs of several payloads, with
    other keys between them."""
    nans = np.array([0x7FF8000000000000, 0xFFF8000000000001,
                     0x7FF0000000000123], np.uint64).view(np.float64)
    rng = np.random.default_rng(5)
    vals = np.concatenate([[0.0, -0.0] * 20, np.tile(nans, 10),
                           rng.integers(1, 50, 60) / 4.0])
    rng.shuffle(vals)
    return pa.table({"f": vals, "v": np.ones(len(vals))})


@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "mesh4"])
def test_a_mesh_group_by_gives_one_group_for_both_zeros_and_all_nans(
        float_key_frame, mesh):
    """On the mesh the partial states are exchanged by the key's hash: a
    group split over two devices would come out twice from the final
    aggregate."""
    from spark_rapids_tpu.expr.functions import col, sum as fsum
    from spark_rapids_tpu.session import TpuSession
    sess = TpuSession({"spark.rapids.tpu.shuffle.partitions": 4,
                       "spark.rapids.sql.test.enabled": True})
    try:
        if mesh:
            from spark_rapids_tpu.parallel.mesh import data_parallel_mesh
            sess.attach_mesh(data_parallel_mesh(4))
        df = sess.create_dataframe(float_key_frame, num_partitions=2)
        got = df.group_by("f").agg(fsum(col("v")).alias("n")) \
            .collect().to_pandas()
        phases = sess.last_query_phases()["phases"]
    finally:
        sess.close()
    assert ("exchange.count" in phases) == mesh    # the ICI exchange
    zero = got[got.f == 0.0]
    nan = got[got.f.isna()]
    assert len(zero) == 1 and zero.n.iloc[0] == 40
    assert len(nan) == 1 and nan.n.iloc[0] == 30
    assert got.n.sum() == len(float_key_frame)
    assert len(got) == 2 + len(set(
        float_key_frame.column("f").to_numpy()[
            ~np.isnan(float_key_frame.column("f").to_numpy())]) - {0.0})


def test_heartbeats():
    hb = HeartbeatManager(timeout_s=0.05)
    hb.register(1)
    hb.register(2)
    assert hb.live_peers() == [1, 2]
    import time
    time.sleep(0.06)
    hb.heartbeat(2)
    assert hb.live_peers() == [2]


def test_ici_exchange_cpu_mesh():
    import jax
    from jax.sharding import Mesh
    from spark_rapids_tpu.shuffle.ici import (ici_all_to_all_exchange,
                                              shard_table, unshard_table)
    devices = np.array(jax.devices()[:8])
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = Mesh(devices, ("dp",))
    t = _host_table(256, seed=3)
    dt_ = DeviceTable.from_host(t, min_bucket=8, capacity=256)
    sharded = shard_table(dt_, mesh)
    out = ici_all_to_all_exchange(sharded, ["k"], mesh)
    assert int(out.num_rows) == 256
    merged = unshard_table(out).to_host()
    # same multiset of rows
    got = sorted(zip(merged.column("k").values.tolist(),
                     np.round(merged.column("v").values, 9).tolist()))
    exp = sorted(zip(t.column("k").values.tolist(),
                     np.round(t.column("v").values, 9).tolist()))
    assert got == exp
    # keys co-located per shard: rows for one key stay in one shard block
    n = 8
    per = out.capacity // n
    kvals = np.asarray(merged.column("k").values)
    mask = np.asarray(out.row_mask)
    shard_of = np.repeat(np.arange(n), per)
    key_shards = {}
    flat_k = np.asarray(unshard_table(out).columns[0].data)
    for i in np.nonzero(mask)[0]:
        key_shards.setdefault(int(flat_k[i]), set()).add(int(shard_of[i]))
    assert all(len(s) == 1 for s in key_shards.values())


def test_dcn_mock_transport_device_to_device():
    """Cross-host accelerated tier, mocked (round-2 missing #6; reference:
    UCX.scala:69 device-to-device block movement; protocol testing via
    mocks as in RapidsShuffleTestHelper): blocks stay device-resident,
    fetch lands them on the consumer's device, per-link bytes are
    accounted, and a missing block raises fetch-failed."""
    import jax
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostColumn, HostTable
    from spark_rapids_tpu.shuffle.dcn import DcnShuffleTransport, \
        MockDcnFabric
    from spark_rapids_tpu.shuffle.transport import BlockId, \
        ShuffleFetchFailedException
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 virtual devices")
    fabric = MockDcnFabric()
    a = DcnShuffleTransport(fabric, "host-a", device=devs[0])
    b = DcnShuffleTransport(fabric, "host-b", device=devs[1])
    rng = np.random.default_rng(0)
    t = DeviceTable.from_host(HostTable(
        ["k", "v"], [HostColumn(dt.LONG, rng.integers(0, 9, 64)),
                     HostColumn(dt.DOUBLE, rng.normal(size=64))]), 8)
    t = jax.device_put(t, devs[0])
    a.publish_table(BlockId(1, 0, 0), t)
    got = dict(b.fetch_tables([BlockId(1, 0, 0)]))[BlockId(1, 0, 0)]
    # landed on the CONSUMER's device, no host serialization in between
    assert devs[1] in got.row_mask.devices()
    assert got.to_host().column("v").values.tolist() == \
        t.to_host().column("v").values.tolist()
    assert fabric.link_bytes[("host-a", "host-b")] > 0
    with pytest.raises(ShuffleFetchFailedException):
        list(b.fetch_tables([BlockId(1, 9, 9)]))
    # failure injection hook (transport-mock testing surface)
    calls = []
    def fault(src, dst, blk):
        calls.append(blk)
        raise ShuffleFetchFailedException(blk, "injected DCN fault")
    fabric.fault = fault
    with pytest.raises(ShuffleFetchFailedException, match="injected"):
        list(b.fetch_tables([BlockId(1, 0, 0)]))
    assert calls == [BlockId(1, 0, 0)]
