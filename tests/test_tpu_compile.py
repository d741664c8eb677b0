"""Programs of the main path, compiled for a TPU v5e that is described, not
attached (on-chip-measurement guide, section 2, rehearsal 3).

The chip's compiler is installed beside the CPU backend the tests run on, so
what it refuses — a Pallas block that does not fit VMEM, an int64 where
Mosaic wants an int32, a collective it cannot partition — fails here at no
chip time. Nothing runs, so these say nothing about results or speed.

Engine programs are compiled at 2^13 rows (compile seconds grow with the
shape; only the Pallas kernel needs its real 2^23-row column to show the VMEM
limit). The only sort compiled here is the top-n's chunk sort: full-width
sorts take minutes on this compiler (ROADMAP A1).

The topology is described inside a module-scoped fixture, so every xdist
worker collects the same tests and only the worker that runs this file loads
libtpu. Keep every such test in THIS file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

ROWS = 1 << 13


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to JAX's persistent cache
    # but cannot be read back without the chip; keep these silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sess():
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": ROWS,
                    # the static plan: the nodes below are looked up in it
                    "spark.rapids.tpu.aqe.enabled": False})
    yield s
    s.close()


def _shapes(tree, sharding):
    """The pytree with every array leaf replaced by its shape on the chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if hasattr(x, "shape") and hasattr(x, "dtype") else x, tree)


def _compile(fn, args, sharding, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*_shapes(args, sharding)).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _find(plan, cls):
    if isinstance(plan, cls):
        return plan
    for c in list(plan.children) + list(getattr(plan, "chain", ())):
        hit = _find(c, cls)
        if hit is not None:
            return hit
    return None


def _sort_widths(jaxpr):
    """Rows every sort in a jaxpr spans, loop and call bodies included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            out.append(eqn.invars[0].aval.shape[eqn.params["dimension"]])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_sort_widths(sub))
    return out


def _table(rng, n):
    return pa.table({"k": np.arange(n, dtype=np.int64) * 4,
                     "g": rng.integers(0, 50, n),
                     "s": rng.choice(np.array(["A", "N", "R"]), n),
                     "v": rng.uniform(0, 10, n)})


def test_q6_whole_stage_step(topo, one_chip):
    """The fused Q6 filter+project+partial-aggregate of the driver entry."""
    from __graft_entry__ import entry
    fn, args = entry()
    _compile(fn, args, one_chip)
    # the donating entry point is what a TPU session dispatches
    # (exec/wholestage.py donation_active); the CPU backend never builds it
    _compile(fn, args, one_chip, donate_argnums=(0,))


def test_grouped_hash_aggregate(topo, one_chip, sess, rng):
    """String- and int-keyed hash group-by: the fused partial stage and the
    final-mode merge program."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.expr.functions import avg, col, count_star, sum
    df = sess.create_dataframe(_table(rng, ROWS - 7))
    q = df.group_by("s", "g").agg(sum(col("v")).alias("t"),
                                  avg(col("v")).alias("m"),
                                  count_star().alias("n"))
    plan = sess._physical(q.logical, device=True)
    final = _find(plan, TpuHashAggregateExec)
    assert final is not None and final.mode == "final", plan.tree_string()
    stage = _find(plan, TpuWholeStageExec)
    assert stage is not None, plan.tree_string()
    batch = next(stage.source.execute_columnar(0))
    partial = stage.batch_fn()
    text = _compile(partial, (batch,), one_chip).as_text()
    _compile(final.batch_fn(), (partial(batch),), one_chip)
    # two conditionals: the bucket resolve's tail (compaction, tail rounds,
    # write-back, skipped where the full rounds left nothing open) and the
    # few-groups branch of `grouped`, whose dense side loops over the live
    # groups
    assert text.count(" conditional(") == 2


def test_grouped_aggregate_by_a_float64_key(topo, one_chip, sess, rng):
    """TPC-H Q18's last group-by holds ``o_totalprice``, a float64 key
    beside a string and an int64: the TPU keeps a float64 as two float32,
    and its compiler has no bitcast of one to 64 integer bits (the key
    hash took one until PR 34: UNIMPLEMENTED, the first run of the cell)."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.expr.functions import col, sum
    df = sess.create_dataframe(_table(rng, ROWS - 7))
    q = df.group_by("s", "k", "v").agg(sum(col("g")).alias("t"))
    plan = sess._physical(q.logical, device=True)
    final = _find(plan, TpuHashAggregateExec)
    stage = _find(plan, TpuWholeStageExec)
    assert final is not None and final.mode == "final" and stage is not None
    batch = next(stage.source.execute_columnar(0))
    partial = stage.batch_fn()
    _compile(partial, (batch,), one_chip)
    _compile(final.batch_fn(with_rounds=True), (partial(batch),), one_chip)


def test_q18s_subquery_stage_passing_its_rows_through(topo, one_chip, sess,
                                                     rng):
    """The pass-through ``srt_stage`` of Q18's ``big`` (``lineitem`` by
    ``l_orderkey``, ``sum(l_quantity)``): the chain below the partial and
    every row its own state, in both entries a TPU session dispatches, and
    the final aggregate over what it leaves."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.expr.functions import col, sum
    df = sess.create_dataframe(_table(rng, ROWS - 7))
    q = df.group_by("k").agg(sum(col("v")).alias("sum_qty"))
    plan = sess._physical(q.logical, device=True)
    final = _find(plan, TpuHashAggregateExec)
    stage = _find(plan, TpuWholeStageExec)
    assert final is not None and final.mode == "final" and stage is not None
    batch = next(stage.source.execute_columnar(0))
    passing = stage.passthrough_fn()
    assert passing is not None
    text = _compile(passing, (batch,), one_chip).as_text()
    _compile(passing, (batch,), one_chip, donate_argnums=(0,))
    _compile(final.batch_fn(with_rounds=True), (passing(batch),), one_chip)
    # no grouping in it: no loop, no branch, no scatter
    assert " while(" not in text and " conditional(" not in text
    assert " scatter(" not in text


def _scatter_results(text):
    """The element types of every scatter's result in compiled HLO text:
    one entry a scatter, a tuple of them where it is variadic."""
    return [tuple(re.findall(r"([a-z]+\d+)\[", m))
            for m in re.findall(r"= (\(.*?\)|\S+) scatter\(", text)]


def test_q18s_final_aggregate_scatters_one_value_buffer_in_64_bits(
        topo, one_chip, sess, rng):
    """Q18's final ``sum(l_quantity)`` by ``l_orderkey`` over one-row
    states: the chip holds a 64-bit scatter as a variadic scatter over a
    pair of 32-bit words. The grouping's scatter branch scatters the row
    counts and the representative positions in int32 (the parent held
    three such pairs: an int64 count, an int64 position and the value), so
    the program's one variadic scatter is the float64 sum's (two float32);
    the bucket-resolve loop's scatters are int32 as well."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.wholestage import TpuWholeStageExec
    from spark_rapids_tpu.expr.functions import col, sum
    df = sess.create_dataframe(_table(rng, ROWS - 7))
    q = df.group_by("k").agg(sum(col("v")).alias("sum_qty"))
    plan = sess._physical(q.logical, device=True)
    final = _find(plan, TpuHashAggregateExec)
    stage = _find(plan, TpuWholeStageExec)
    batch = next(stage.source.execute_columnar(0))
    states = stage.passthrough_fn()(batch)
    text = _compile(final.batch_fn(with_rounds=True), (states,),
                    one_chip).as_text()
    scatters = _scatter_results(text)
    assert [s for s in scatters if len(s) > 1] == [("f32", "f32")], scatters
    assert set(scatters) - {("f32", "f32")} == {("s32",)}, scatters


def test_pk_hash_join(topo, one_chip, sess, rng):
    """FK->PK join on the sort-free slot table: build prep and fused probe
    (exec/joins.py pk_hash_join_fn)."""
    from spark_rapids_tpu.exec.joins import (TpuShuffledHashJoinExec,
                                             _key_view)
    from spark_rapids_tpu.expr.functions import col
    dim = sess.create_dataframe(_table(rng, ROWS // 2)).select(
        col("k").alias("pk"), col("v").alias("w"))
    fact = sess.create_dataframe(_table(rng, ROWS - 7)).select(
        (col("g") * 4).alias("fk"), col("v"))
    q = fact.join(dim, condition=col("fk") == col("pk"))
    plan = sess._physical(q.logical, device=True)
    node = _find(plan, TpuShuffledHashJoinExec)
    assert node is not None, plan.tree_string()
    build = next(node.right.execute_columnar(0))
    probe = next(node.left.execute_columnar(0))
    prep = node._kernels.build_prep_hash_fn()
    build_keys = _key_view(build, node.right_keys)
    _compile(prep, (build_keys,), one_chip)
    slot_row, bv, *_ = prep(build_keys)
    clone, _ = node._canon()
    _compile(clone._kernels.pk_hash_join_fn("inner"),
             (build.canonical(), probe.canonical(),
              _key_view(probe, node.left_keys), slot_row, bv), one_chip)


def test_top_n_chunk_sort(topo, one_chip, rng):
    """Q3-shaped top-n (float64 desc, int asc) over a batch 16 chunks wide,
    at the default 1024-row state capacity: the chunk-winners reduction
    keeps every sort at or under _TOPN_CHUNK rows. The full-width lexsort
    of the same keys compiled for 101 s at 2^14 rows and 532 s at 2^16
    (PR 22 rehearsal), so a regression shows as a timeout."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.exec.sort import _TOPN_CHUNK, TpuTakeOrderedExec
    from spark_rapids_tpu.expr.functions import col
    from spark_rapids_tpu.session import TpuSession
    sess = TpuSession({"spark.rapids.tpu.aqe.enabled": False})
    n = 16 * _TOPN_CHUNK
    df = sess.create_dataframe(_table(rng, n - 7)).select(
        col("k"), col("v"), col("g").cast(dt.INT).alias("d"))
    q = df.sort(col("v").desc(), col("d").asc()).limit(10)
    node = _find(sess._physical(q.logical, device=True), TpuTakeOrderedExec)
    assert node is not None
    batch = next(node.child.execute_columnar(0))
    assert batch.capacity == n
    widths = _sort_widths(jax.make_jaxpr(node._topn_fn("|test"))(batch).jaxpr)
    assert widths and max(widths) <= _TOPN_CHUNK, widths
    _compile(node._topn_fn("|test"), (batch,), one_chip)


@pytest.mark.parametrize("out_cap", [1 << 10, 1 << 17])
def test_compact_shrink_at_a_full_batch(topo, one_chip, rng, out_cap):
    """``shrink_to_fit``'s program at the real 2^20-row batch, into Q1's
    1,024-row bucket and into a join output's 2^17: seconds to compile
    (the ``jnp.cumsum`` form of the permutation took this compiler 16-19 s),
    and no loop in what it compiled."""
    from spark_rapids_tpu.columnar.device import (DeviceTable,
                                                  _compact_shrink_impl)
    from spark_rapids_tpu.columnar.host import HostTable
    small = DeviceTable.from_host(HostTable.from_arrow(_table(rng, 8)))
    full = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((1 << 20,) + x.shape[1:], x.dtype)
        if getattr(x, "ndim", 0) else x, small)
    compiled = _compile(_compact_shrink_impl, (full, out_cap), one_chip,
                        static_argnums=(1,))
    assert "while" not in compiled.as_text()


def test_probe_walk_at_a_full_batch(topo, one_chip):
    """The hash join's chain walk alone at ``sf1.q3``'s largest probe: a
    2^20-row batch against a 2^19-slot table. Seconds to compile (a
    ``jnp.cumsum`` over the batch would cost this compiler 17-31 s), and
    what it compiled holds the two loops — full rounds, tail rounds — and
    no windowed scan."""
    from spark_rapids_tpu.exec.joins import _JoinKernels
    args = (jax.ShapeDtypeStruct((1 << 19,), jnp.int32),
            jax.ShapeDtypeStruct((1 << 18,), jnp.int64),
            jax.ShapeDtypeStruct((1 << 20,), jnp.int64),
            jax.ShapeDtypeStruct((1 << 20,), jnp.bool_))
    text = _compile(_JoinKernels(None).probe_slots_fn(), args,
                    one_chip).as_text()
    assert text.count(" while(") == 2 and "reduce-window(" not in text


def test_join_expand_slot_map_at_q13s_shapes(topo, one_chip):
    """The expand's slot math at ``sf1.q13``'s shapes: a 2^17-row probe
    batch against a 2^21-row build into 2^20 output slots. Seconds to
    compile, and what it compiled holds no loop (a ``searchsorted`` of the
    slots is a binary search of 18 rounds) and no windowed scan."""
    import types
    from spark_rapids_tpu.exec.joins import _JoinKernels

    def slots(mask, b_order, starts, counts):
        probe = types.SimpleNamespace(row_mask=mask, capacity=mask.shape[0])
        build = types.SimpleNamespace(capacity=b_order.shape[0])
        return _JoinKernels(None)._slots(build, probe, b_order, starts,
                                         counts, 1 << 20, True)
    args = (jax.ShapeDtypeStruct((1 << 17,), jnp.bool_),
            jax.ShapeDtypeStruct((1 << 21,), jnp.int32),
            jax.ShapeDtypeStruct((1 << 17,), jnp.int64),
            jax.ShapeDtypeStruct((1 << 17,), jnp.int64))
    text = _compile(slots, args, one_chip).as_text()
    assert " while(" not in text and "reduce-window(" not in text


@pytest.mark.parametrize("keys", [
    {"k": pa.array([7, 8], pa.int64())},                  # Q18's, Q3's
    {"rf": ["A", "N"], "ls": ["F", "O"]}], ids=["int64", "q1_strings"])
def test_bucket_resolve_at_a_full_batch(topo, one_chip, keys):
    """The group-by's bucket-resolve loops alone over a full 2^20-row
    batch, by an int64 key and by Q1's two string keys: seconds to compile,
    and what compiled holds the two loops — full rounds, tail rounds — no
    sort and no windowed scan (the compaction is the blocked prefix sum; a
    ``jnp.cumsum`` over the batch costs this compiler ~20 s, as the one
    ``_hash_group_ids`` still takes for its group ids does)."""
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.exec import aggregate
    table = DeviceTable.from_host(HostTable.from_arrow(pa.table(keys)),
                                  capacity=1 << 20)

    def loops(tb):
        h, words = aggregate._hashed_key_words(tb, list(keys))
        return aggregate._resolve_buckets(h, words, tb.row_mask)
    text = _compile(loops, (table,), one_chip).as_text()
    assert text.count(" while(") == 2 and " sort(" not in text
    assert "reduce-window(" not in text


def test_pallas_axpy_full_column(topo, one_chip, monkeypatch):
    """The gridded Pallas kernel at a 2^23-row SF1 lineitem bucket: the
    ungridded kernel ran out of VMEM from 2^22 rows up."""
    from spark_rapids_tpu.udf import examples
    # the kernel asks the default backend whether to interpret; here that
    # is the CPU, and the chip's lowering is what is being compiled
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jax.ShapeDtypeStruct((1 << 23,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(examples._pallas_axpy_device).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ici_exchange_on_four_chips(topo, sess, rng):
    """The shard_map all-to-all of the ICI exchange, partitioned over the
    2x2 host's four chips."""
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.shuffle.ici import exchange_program
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("dp",))
    assert mesh.size == 4
    table = DeviceTable.from_host(
        HostTable.from_arrow(_table(rng, 4 * ROWS - 7)), ROWS)
    fn = exchange_program(table.columns, table.names, ["k"], mesh, "dp",
                          quota=ROWS // 2)
    rows = NamedSharding(mesh, P("dp"))
    compiled = fn.lower(*_shapes((table.columns, table.row_mask),
                                 rows)).compile()
    assert "all-to-all" in compiled.as_text()
    per_device = compiled.memory_analysis()
    assert per_device.argument_size_in_bytes \
        < sum(x.nbytes for x in jax.tree_util.tree_leaves(table)) // 2


#: a float64 bitcast to 64 integer bits: the TPU's X64 rewriter has none
_F64_TO_INT64 = re.compile(r"= [su]64\[[^\]]*\]\S* bitcast-convert\(f64")


@pytest.mark.parametrize("program", ["partition_ids", "all_to_all"])
def test_q18s_final_keys_hash_exchange_on_four_chips(topo, rng, program):
    """TPC-H Q18's last group-by on the mesh exchanges its partial states
    by five keys: the string ``c_name``, two int64 keys, the int32 date and
    the float64 ``o_totalprice``. The exchange hashed a float64 key through
    a 64-bit bitcast until PR 38 (UNIMPLEMENTED on the chip)."""
    from spark_rapids_tpu.columnar.device import DeviceTable
    from spark_rapids_tpu.columnar.host import HostTable
    from spark_rapids_tpu.exec.exchange import _pid_program
    from spark_rapids_tpu.shuffle.ici import exchange_program
    n = 4 * ROWS
    t = pa.table({
        "c_name": [f"Customer#{i:09d}" for i in rng.integers(1, 150_000, n)],
        "c_custkey": rng.integers(1, 150_000, n),
        "o_orderkey": rng.integers(1, 6_000_000, n),
        "o_orderdate": pa.array(rng.integers(8_000, 10_600, n).astype(
            np.int32)).cast(pa.date32()),
        "o_totalprice": rng.integers(85_000, 56_000_000, n) / 100.0,
        "sum_qty": rng.integers(1, 51, n).astype(np.float64)})
    table = DeviceTable.from_host(HostTable.from_arrow(t), n)
    keys = ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
            "o_totalprice"]
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("dp",))
    if program == "partition_ids":
        fn = jax.jit(_pid_program(keys, 4))
        args = _shapes((table,), SingleDeviceSharding(topo.devices[0]))
    else:
        fn = exchange_program(table.columns, table.names, keys, mesh, "dp",
                              quota=ROWS // 2)
        args = _shapes((table.columns, table.row_mask),
                       NamedSharding(mesh, P("dp")))
    lowered = fn.lower(*args)
    assert not _F64_TO_INT64.search(lowered.as_text(dialect="hlo"))
    compiled = lowered.compile()
    if program == "all_to_all":
        assert "all-to-all" in compiled.as_text()


def test_like_nfa_at_a_full_batch(topo, one_chip):
    """Q13's ``NOT LIKE '%special%requests%'`` NFA alone over a full
    2^20-row batch of 128-byte strings, the bucket ``o_comment`` takes:
    one loop, a step a byte column, and no gather anywhere (a gather of a
    (n, states) mask row a step made it 67 times slower on the chip)."""
    from spark_rapids_tpu.expr.regex import compile_device_nfa

    class Ctx:
        xp = jnp

    class Col:
        def __init__(self, values, lengths):
            self.values, self.lengths = values, lengths

    nfa = compile_device_nfa("^.*special.*requests.*$")
    args = (jax.ShapeDtypeStruct((1 << 20, 128), jnp.uint8),
            jax.ShapeDtypeStruct((1 << 20,), jnp.int32))
    text = _compile(lambda v, n: nfa.matches(Ctx, Col(v, n)), args,
                    one_chip).as_text()
    assert text.count(" while(") == 1 and " gather(" not in text
