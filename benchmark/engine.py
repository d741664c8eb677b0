"""Everything the benchmark takes from the program: the session the
configuration describes, the frames over the Parquet files, the query by
name, the executed plan's node names, and its fallback counter. Nothing
else in ``benchmark/`` imports ``spark_rapids_tpu``.

The session is strict: an operator planned off the device raises, and
runtime host fallback and quarantine are off (they are in every
configuration's ``session_conf``), so a device failure fails the query
instead of being answered by the host engine.
"""
import os

from .cells import REPO

DEFAULT_CACHE_DIR = os.path.join(REPO, ".jax_compile_cache")

#: plan nodes that are not Tpu*Exec by name and still belong to a fully
#: device-planned query: the two transitions, and the host Parquet reader
#: that feeds HostToDeviceExec where device decode does not apply (Q6's
#: pushed-down filter, `customer`'s strings)
NON_TPU_NODES = frozenset({"DeviceToHostExec", "HostToDeviceExec",
                           "CpuScanExec"})


def build_native_library() -> bool:
    """g++-build the program's native library where the checkout has none
    (it is git-ignored); part of set-up. True when a library is loaded."""
    from spark_rapids_tpu import native
    return native.get_lib() is not None


def open_session(config: dict):
    """The session of a configuration file: its ``session_conf`` on top of
    the defaults, its mesh attached. XLA's compile cache is where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_compile_cache``
    (a fixed path: the path is part of the cache key)."""
    from spark_rapids_tpu.session import TpuSession

    conf = dict(config["session_conf"])
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        conf["spark.rapids.tpu.compile.cacheDir"] = DEFAULT_CACHE_DIR
    sess = TpuSession(conf)
    mesh = config.get("mesh")
    if mesh:
        from spark_rapids_tpu.parallel.mesh import data_parallel_mesh
        if mesh["kind"] != "data_parallel":
            raise ValueError(f"unknown mesh kind {mesh['kind']!r}")
        sess.attach_mesh(data_parallel_mesh(int(mesh["devices"])))
    # collect() plans inside; keep the plan it executed so that its nodes
    # can be read afterwards (AQE settles the tree only while the query runs)
    sess.executed_plan = None
    plan_physical = sess._physical

    def recording(logical, device=None):
        sess.executed_plan = plan_physical(logical, device)
        return sess.executed_plan
    sess._physical = recording
    return sess


def build_query(sess, root: str, config: dict, traffic: dict):
    """The traffic's query over frames on the Parquet tables it reads;
    ``.collect()`` on what this returns is the timed entry."""
    from spark_rapids_tpu.tools import tpch

    frames = {}
    for name in traffic["columns"]:
        df = sess.read_parquet(os.path.join(root, name))
        if df.num_partitions() != config["files_per_table"]:
            raise RuntimeError(
                f"{name}: read as {df.num_partitions()} partitions, the "
                f"configuration has {config['files_per_table']} files")
        frames[name] = df
    return tpch.QUERIES[traffic["query"]](frames)


def executed_nodes(plan) -> list:
    """Node names of a plan a collect() executed (``sess.executed_plan``)."""
    if hasattr(plan, "final_plan"):
        plan = plan.final_plan()
    return [ln.split("[")[0].split()[0]
            for ln in plan.tree_string().splitlines() if ln.strip()]


def plan_faults(nodes, plan_rules: dict) -> list:
    """What is wrong with an executed plan, as strings: host operators
    outside the allowed names, or none of a group the cell must plan."""
    allowed = NON_TPU_NODES | set(plan_rules.get("also_allowed", ()))
    faults = [f"host operator {n}" for n in nodes
              if not n.startswith("Tpu") and n not in allowed]
    for group in plan_rules.get("must_hold", ()):
        if not any(n.startswith(g) for n in nodes for g in group):
            faults.append(f"none of {'/'.join(group)} planned")
    return faults


def host_fallbacks() -> int:
    from spark_rapids_tpu.exec.fallback import fallback_stats
    return int(fallback_stats()["host_fallbacks"])
