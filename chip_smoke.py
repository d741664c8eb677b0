#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the engine still starts on the chip.

One process, no subprocess. Drives the main path once at TPC-H SF1 on one TPU:
Parquet on disk -> TpuSession.read_parquet -> plan/overrides -> fused stages,
hash group-by, hash join, exchange, sort -> DataFrame.collect(), for Q6, Q1,
Q3 and Q4, then the Pallas UDF kernel over SF1 columns. Every answer is
compared with an independent pandas computation over the same Parquet files.
The session is strict: an operator planned off the device raises
(spark.rapids.sql.test.enabled), and runtime host fallback and quarantine are
off, so a device failure fails the script instead of being re-run on the host.

Earlier stdout lines are one JSON object each (environment, data, per-query
cold/warm wall, programs compiled, compile seconds, max relative error). The
LAST line is {"ok": true, "device": {...}} and is printed only when every
phase passed on a TPU. Without a TPU the script exits 2 and prints no result.

    python chip_smoke.py                        # one chip, as the driver runs it
    python chip_smoke.py --chips 4              # only: Q3 on one device, then
                                                # Q3 over a 4-device ICI mesh
    python chip_smoke.py --rehearse-cpu --sf 0.01   # control flow on the CPU
                                                    # backend; never prints ok
"""
import argparse
import glob
import json
import os
import sys
import time

import jax

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pyarrow.parquet as pq  # noqa: E402

from spark_rapids_tpu import native  # noqa: E402
from spark_rapids_tpu.columnar import dtypes as dt  # noqa: E402
from spark_rapids_tpu.exec.fallback import fallback_stats  # noqa: E402
from spark_rapids_tpu.expr.functions import col  # noqa: E402
from spark_rapids_tpu.session import TpuSession  # noqa: E402
from spark_rapids_tpu.tools import tpch  # noqa: E402
from spark_rapids_tpu.udf import examples as udf_examples  # noqa: E402
from spark_rapids_tpu.utils.compile_cache import (cache_stats,  # noqa: E402
                                                  persistent_cache_dir)

#: both git-ignored, fixed, inside the checkout
DATA_DIR = os.path.join(REPO, ".smoke_data")
DEFAULT_CACHE_DIR = os.path.join(REPO, ".jax_compile_cache")

TABLES = ("lineitem", "orders", "customer")
FILES_PER_TABLE = 2          # read_parquet -> one partition per file

#: float64 aggregates against pandas, relative. Counts, keys and integer
#: columns must be exact. The engine sums in another order than pandas, which
#: alone costs ~1e-13 over 6M rows; a v5e has no f64 unit, and the measured
#: maximum is printed per query whatever it is (ISSUE 22, ROADMAP A5).
F64_REL_BOUND = 1e-9
#: the Pallas kernel computes a*x+y in float32; a fused multiply-add rounds
#: once where numpy rounds twice (1 ulp = 1.2e-7 relative)
F32_REL_BOUND = 1e-6

#: plan nodes that are not Tpu*Exec by name and still belong to a fully
#: device-planned query: the two transitions, and the host Parquet reader
#: that feeds HostToDeviceExec where device decode does not apply
NON_TPU_NODES = {"DeviceToHostExec", "HostToDeviceExec", "CpuScanExec"}
#: with a mesh attached the planner gathers the final top-n (40 rows for Q3)
#: into one partition through the host-staged exchange; only these two node
#: names, only in the --chips 4 plan
MESH_HOST_GATHER = {"ShuffleStageExec", "ShuffleExchangeExec"}

def emit(**obj):
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# XLA's own compile clock (jax.monitoring): what cache_stats() cannot see —
# programs outside cached_jit, and persistent-cache hits
# ---------------------------------------------------------------------------
class XlaCompileClock:
    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        """The engine's cache_stats() and XLA's own counters, one dict."""
        return {**cache_stats(), "xla_compiles": self.compiles,
                "xla_compile_seconds": self.seconds,
                "xla_persistent_cache_hits": self.cache_hits}


# ---------------------------------------------------------------------------
# environment
# ---------------------------------------------------------------------------
def report_environment(rehearse: bool):
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    stats = dev.memory_stats()
    if dev.platform == "tpu" and not (stats and "bytes_limit" in stats):
        raise RuntimeError(f"TPU device reports no bytes_limit: {stats!r}")
    from importlib import metadata
    versions = {"jax": jax.__version__,
                "jaxlib": metadata.version("jaxlib"),
                "libtpu": metadata.version("libtpu")}
    so_before = set(glob.glob(os.path.join(
        os.path.dirname(native.__file__), "_srtpu_native_*.so")))
    lib = native.get_lib()
    so_after = set(glob.glob(os.path.join(
        os.path.dirname(native.__file__), "_srtpu_native_*.so")))
    native_state = "absent" if lib is None else \
        ("built" if so_after - so_before else "loaded")
    env_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    emit(phase="environment", device=device,
         hbm_bytes_limit=(stats or {}).get("bytes_limit"),
         versions=versions, native_library=native_state,
         compile_cache_dir=env_cache or DEFAULT_CACHE_DIR,
         compile_cache_from_env=bool(env_cache), rehearse_cpu=rehearse)
    return device


# ---------------------------------------------------------------------------
# data: seeded generator -> Parquet, written once
# ---------------------------------------------------------------------------
def ensure_data(sf: float, seed: int) -> str:
    root = os.path.join(DATA_DIR, f"sf{sf:g}_seed{seed}")
    marker = os.path.join(root, "_complete.json")
    t0 = time.perf_counter()
    if not os.path.exists(marker):
        tables = tpch.gen_all(sf, seed=seed)
        for name in TABLES:
            d = os.path.join(root, name)
            os.makedirs(d, exist_ok=True)
            t = tables[name]
            per = -(-t.num_rows // FILES_PER_TABLE)
            for i in range(FILES_PER_TABLE):
                pq.write_table(t.slice(i * per, per),
                               os.path.join(d, f"part-{i}.parquet"))
        with open(marker, "w") as f:
            json.dump({"sf": sf, "seed": seed,
                       "rows": {n: tables[n].num_rows for n in TABLES}}, f)
        made = "generated"
    else:
        made = "reused"
    with open(marker) as f:
        meta = json.load(f)
    emit(phase="data", sf=sf, seed=seed, dir=os.path.relpath(root, REPO),
         rows=meta["rows"], files_per_table=FILES_PER_TABLE, data=made,
         seconds=round(time.perf_counter() - t0, 3))
    return root


def read_pandas(root: str, table: str, columns) -> pd.DataFrame:
    return pq.read_table(os.path.join(root, table),
                         columns=list(columns)).to_pandas()


def _days(series: pd.Series) -> np.ndarray:
    return series.to_numpy().astype("datetime64[D]").astype(np.int64)


def _day(iso: str) -> int:
    """Days since the epoch, computed here (the queries under test carry
    their own table of these)."""
    return int(np.datetime64(iso, "D").astype(np.int64))


# ---------------------------------------------------------------------------
# independent references (pandas over the same Parquet files)
# ---------------------------------------------------------------------------
def ref_q6(root):
    li = read_pandas(root, "lineitem", ["l_shipdate", "l_discount",
                                        "l_quantity", "l_extendedprice"])
    sd = _days(li.l_shipdate)
    m = ((sd >= _day("1994-01-01")) & (sd < _day("1995-01-01"))
         & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
         & (li.l_quantity < 24.0))
    return pd.DataFrame({"revenue": [
        float((li.l_extendedprice[m] * li.l_discount[m]).sum())]})


def ref_q1(root):
    li = read_pandas(root, "lineitem", [
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax"])
    li = li[_days(li.l_shipdate) <= _day("1998-09-02")]
    li = li.assign(disc_price=li.l_extendedprice * (1.0 - li.l_discount))
    li = li.assign(charge=li.disc_price * (1.0 + li.l_tax))
    out = li.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"))
    return out.sort_values(["l_returnflag", "l_linestatus"]) \
              .reset_index(drop=True)


def ref_q3(root):
    cust = read_pandas(root, "customer", ["c_custkey", "c_mktsegment"])
    orders = read_pandas(root, "orders", ["o_orderkey", "o_custkey",
                                          "o_orderdate", "o_shippriority"])
    li = read_pandas(root, "lineitem", ["l_orderkey", "l_shipdate",
                                        "l_extendedprice", "l_discount"])
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = orders[_days(orders.o_orderdate) < _day("1995-03-15")]
    li = li[_days(li.l_shipdate) > _day("1995-03-15")]
    j = cust.merge(orders, left_on="c_custkey", right_on="o_custkey") \
            .merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j = j.assign(revenue=j.l_extendedprice * (1.0 - j.l_discount))
    out = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                    as_index=False).agg(revenue=("revenue", "sum"))
    return out.sort_values(["revenue", "o_orderdate"],
                           ascending=[False, True]) \
              .head(10).reset_index(drop=True)


def ref_q4(root):
    orders = read_pandas(root, "orders", ["o_orderkey", "o_orderdate",
                                          "o_orderpriority"])
    li = read_pandas(root, "lineitem", ["l_orderkey", "l_commitdate",
                                        "l_receiptdate"])
    od = _days(orders.o_orderdate)
    orders = orders[(od >= _day("1993-07-01")) & (od < _day("1993-10-01"))]
    late = li.l_orderkey[_days(li.l_commitdate) < _days(li.l_receiptdate)]
    orders = orders[orders.o_orderkey.isin(late.unique())]
    out = orders.groupby("o_orderpriority", as_index=False) \
                .agg(order_count=("o_orderkey", "size"))
    return out.sort_values("o_orderpriority").reset_index(drop=True)


REFERENCES = {"q6": ref_q6, "q1": ref_q1, "q3": ref_q3, "q4": ref_q4}


def compare(name: str, got: pd.DataFrame, ref: pd.DataFrame) -> float:
    """Rows, column set, non-float columns exact and in order; float64
    columns within F64_REL_BOUND. Returns the maximum relative error."""
    if list(got.columns) != list(ref.columns):
        raise AssertionError(f"{name}: columns {list(got.columns)} != "
                             f"{list(ref.columns)}")
    if len(got) != len(ref):
        raise AssertionError(f"{name}: {len(got)} rows != {len(ref)}")
    worst = 0.0
    for c in ref.columns:
        g, r = got[c].to_numpy(), ref[c].to_numpy()
        if r.dtype.kind == "f":
            err = np.abs(g.astype(np.float64) - r) \
                / np.maximum(np.abs(r), np.finfo(np.float64).tiny)
            worst = max(worst, float(err.max()))
        elif r.dtype.kind == "M" or g.dtype.kind == "M" or g.dtype == object:
            if [str(x)[:10] for x in g] != [str(x)[:10] for x in r]:
                raise AssertionError(f"{name}: column {c} differs:\n"
                                     f"{got}\n{ref}")
        elif not np.array_equal(g.astype(np.int64), r.astype(np.int64)):
            raise AssertionError(f"{name}: column {c} differs:\n{got}\n{ref}")
    if not worst <= F64_REL_BOUND:
        raise AssertionError(f"{name}: max relative error {worst:.3e} above "
                             f"{F64_REL_BOUND:g}:\n{got}\n{ref}")
    return worst


# ---------------------------------------------------------------------------
# the strict session, and what a query must leave behind
# ---------------------------------------------------------------------------
def strict_session(extra=None) -> TpuSession:
    conf = {
        "spark.rapids.sql.test.enabled": True,
        "spark.rapids.tpu.fallback.enabled": False,
        "spark.rapids.tpu.fallback.quarantine.enabled": False,
    }
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        conf["spark.rapids.tpu.compile.cacheDir"] = DEFAULT_CACHE_DIR
    conf.update(extra or {})
    sess = TpuSession(conf)
    # collect() plans inside; keep what it executed so the plan can be read
    # afterwards (AQE only settles the tree while the query runs)
    sess.executed_plans = []
    plan_physical = sess._physical

    def recording(logical, device=None):
        plan = plan_physical(logical, device)
        sess.executed_plans.append(plan)
        return plan
    sess._physical = recording
    return sess


def read_tables(sess: TpuSession, root: str):
    dfs = {n: sess.read_parquet(os.path.join(root, n)) for n in TABLES}
    for n, df in dfs.items():
        if df.num_partitions() < 2:
            raise AssertionError(f"{n}: read as one partition")
    return dfs


def executed_tree(sess: TpuSession) -> str:
    plan = sess.executed_plans[-1]
    if hasattr(plan, "final_plan"):
        plan = plan.final_plan()
    return plan.tree_string()


def check_device_plan(name: str, tree: str, must_hold=(), also_allowed=()):
    nodes = [ln.split("[")[0].split()[0] for ln in tree.splitlines()
             if ln.strip()]
    allowed = NON_TPU_NODES | set(also_allowed)
    off = [n for n in nodes if not n.startswith("Tpu") and n not in allowed]
    if off:
        raise AssertionError(f"{name}: host operators {off} in\n{tree}")
    if "Parquet[" not in tree:
        raise AssertionError(f"{name}: no Parquet scan in\n{tree}")
    for group in must_hold:
        if not any(n.startswith(g) for n in nodes for g in group):
            raise AssertionError(f"{name}: none of {group} in\n{tree}")
    return nodes


def run_query(sess, clock, name, dfs, root, must_hold=(), also_allowed=()):
    """collect() twice (cold, then warm in the same process), check the plan
    and the fallback counters, compare both answers with pandas."""
    df = tpch.QUERIES[name](dfs)
    marks, walls, answers = [clock.snapshot()], [], []
    for _ in range(2):
        t0 = time.perf_counter()
        answers.append(df.collect().to_pandas())
        walls.append(time.perf_counter() - t0)
        marks.append(clock.snapshot())
    fallbacks = fallback_stats()
    if fallbacks["host_fallbacks"] != 0:
        raise AssertionError(f"{name}: host fallbacks {fallbacks}")
    tree = executed_tree(sess)
    nodes = check_device_plan(name, tree, must_hold, also_allowed)
    ref = REFERENCES[name](root)
    err = max(compare(name, a, ref) for a in answers)

    def delta(i, key):   # what collect() number i added to a counter
        return marks[i + 1][key] - marks[i][key]
    emit(phase="query", query=name, rows=len(ref),
         cold_wall_s=round(walls[0], 3), warm_wall_s=round(walls[1], 3),
         programs_compiled=delta(0, "compiles"),
         first_call_seconds=round(delta(0, "compile_seconds"), 3),
         warm_programs_compiled=delta(1, "compiles"),
         xla_compiles=delta(0, "xla_compiles"),
         xla_compile_seconds=round(delta(0, "xla_compile_seconds"), 3),
         xla_persistent_cache_hits=delta(0, "xla_persistent_cache_hits"),
         warm_xla_compiles=delta(1, "xla_compiles"),
         max_rel_err=err, rel_bound=F64_REL_BOUND,
         host_fallbacks=fallbacks["host_fallbacks"], plan_nodes=len(nodes),
         scan="device-decode" if "TpuParquetScanExec" in nodes else "host")
    return answers[0]


def run_pallas_udf(sess, dfs, root, on_tpu: bool):
    """pallas_axpy over three SF1 lineitem columns as float32, against its
    numpy host function."""
    q = dfs["lineitem"].select(udf_examples.pallas_axpy(
        col("l_quantity").cast(dt.FLOAT),
        col("l_extendedprice").cast(dt.FLOAT),
        col("l_discount").cast(dt.FLOAT)).alias("r"))
    t0 = time.perf_counter()
    got = q.collect().column("r").to_numpy()
    wall = time.perf_counter() - t0
    check_device_plan("pallas_axpy", executed_tree(sess))
    if fallback_stats()["host_fallbacks"] != 0:
        raise AssertionError(f"pallas_axpy: {fallback_stats()}")
    li = read_pandas(root, "lineitem",
                     ["l_quantity", "l_extendedprice", "l_discount"])
    want = udf_examples._pallas_axpy_host(
        li.l_quantity.to_numpy(), li.l_extendedprice.to_numpy(),
        li.l_discount.to_numpy())
    if got.shape != want.shape or got.dtype != np.float32:
        raise AssertionError(f"pallas_axpy: {got.shape} {got.dtype}")
    if not np.isfinite(got).all():
        raise AssertionError("pallas_axpy: non-finite values")
    err = float((np.abs(got - want) / np.abs(want)).max())
    if not err <= F32_REL_BOUND:
        raise AssertionError(f"pallas_axpy: max relative error {err:.3e}")
    # what the engine dispatched for this backend, read from the lowering
    x = jax.ShapeDtypeStruct((1 << 23,), np.float32)
    mosaic = "tpu_custom_call" in jax.jit(
        udf_examples._pallas_axpy_device).lower(x, x, x).as_text()
    if on_tpu and not mosaic:
        raise AssertionError("pallas_axpy did not lower to a Mosaic kernel")
    emit(phase="pallas_udf", rows=int(got.shape[0]), wall_s=round(wall, 3),
         max_rel_err=err, rel_bound=F32_REL_BOUND,
         compiled_kernel=mosaic, interpreted=not mosaic)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def one_chip(args, clock, on_tpu):
    root = ensure_data(args.sf, args.seed)
    sess = strict_session()
    emit(phase="session", engine_cache_dir=persistent_cache_dir(),
         jax_compilation_cache_dir=jax.config.jax_compilation_cache_dir)
    try:
        dfs = read_tables(sess, root)
        for name in ("q6", "q1", "q3", "q4"):
            run_query(sess, clock, name, dfs, root)
        run_pallas_udf(sess, dfs, root, on_tpu)
    finally:
        sess.close()
    emit(phase="totals", **clock.snapshot())


def four_chips(args, clock, on_tpu):
    """Only what exists across chips: Q3 on one device (the comparison),
    then Q3 over a 4-device ICI mesh. The planner's broadcast threshold is
    off so that both inputs of each join go through the exchange."""
    from spark_rapids_tpu.parallel.mesh import data_parallel_mesh
    root = ensure_data(args.sf, args.seed)
    no_broadcast = {"spark.rapids.tpu.autoBroadcastJoinThreshold": -1}

    sess = strict_session(no_broadcast)
    try:
        single = run_query(sess, clock, "q3", read_tables(sess, root), root)
    finally:
        sess.close()

    def peaks():
        return [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.devices()[:4]]

    sess = strict_session({**no_broadcast,
                           "spark.rapids.tpu.shuffle.partitions": 4})
    sess.attach_mesh(data_parallel_mesh(4))
    try:
        before = peaks()
        meshed = run_query(
            sess, clock, "q3", read_tables(sess, root), root,
            must_hold=[("TpuShuffleExchangeExec", "TpuMeshStageExec")],
            also_allowed=MESH_HOST_GATHER)
        exchanges = exchanged_rows(sess.executed_plans[-1])
        after = peaks()
    finally:
        sess.close()
    compare("q3 mesh vs one device", meshed, single)
    # every exchange left rows on all four mesh shards, and (where the
    # backend reports memory) devices 1-3, idle during the one-device run,
    # now show the shards in their peak
    if not exchanges or any(len(r) != 4 or min(r) <= 0
                            for _, r in exchanges):
        raise AssertionError(f"exchanged rows per device: {exchanges}")
    if on_tpu and any(a is None or a - (b or 0) < (1 << 20)
                      for a, b in zip(after[1:], before[1:])):
        raise AssertionError(f"peak bytes per device {before} -> {after}")
    emit(phase="mesh", devices=4,
         exchanged_rows_by_device=dict(exchanges),
         peak_bytes_by_device_before=before, peak_bytes_by_device_after=after)


def exchanged_rows(plan):
    """(exchange keys, rows per destination device) of every ICI exchange
    in an executed plan, from the exchange's own per-shard counts."""
    if hasattr(plan, "final_plan"):
        plan = plan.final_plan()
    out, stack, seen = [], [plan], set()
    while stack:
        node = stack.pop()
        if node is None or id(node) in seen:
            continue
        seen.add(id(node))
        stack.extend(node.children)
        # AQE stage leaves/readers, fused chains and mesh stages hide subtrees
        stack.extend(getattr(node, a, None)
                     for a in ("inner", "stage", "exchange"))
        stack.extend(getattr(node, "chain", ()))
        if type(node).__name__ == "TpuShuffleExchangeExec" \
                and node._skew_rows is not None:
            out.append((",".join(node.partitioning.key_names),
                        list(node._skew_rows)))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the control flow on a backend without a TPU; "
                         "never prints the ok line")
    args = ap.parse_args()

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(f"chip_smoke: JAX found no TPU (platform={platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if on_tpu and args.sf != 1.0:
        print("chip_smoke: --sf is for --rehearse-cpu; the chip run is SF1",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(jax.devices())}", file=sys.stderr)
        return 2

    clock = XlaCompileClock()
    t0 = time.perf_counter()
    device = report_environment(args.rehearse_cpu)
    if args.chips == 4:
        four_chips(args, clock, on_tpu)
    else:
        one_chip(args, clock, on_tpu)
    emit(phase="done", seconds=round(time.perf_counter() - t0, 3))
    if not on_tpu:
        print("chip_smoke: rehearsal finished; no result is printed without "
              "a TPU", file=sys.stderr)
        return 2
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
