"""Compile-time amortization: the canonical shape-bucket ladder and the
two tiers of the program cache (docs/compilation.md).

- bucket-ladder unit tests (monotonic, covering, bounded waste, conf
  round-trip through a session),
- the in-process AOT table of the mesh programs (``aot_program``): one
  executable a key, bounded, emptied by ``clear_cache()``,
- XLA's persistent cache across a REAL subprocess boundary: the second
  process's compile requests are all served from it,
- where the cache is placed (environment first, then the conf), and that
  nothing but XLA's entries and ``quarantine.json`` is kept there: what an
  older version's manifest / export tier left behind is not touched.
"""
import builtins
import json
import os
import pathlib
import subprocess
import sys
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.device import (BucketPolicy, bucket_rows,
                                              configure_buckets,
                                              current_bucket_policy,
                                              resolve_min_bucket)
from spark_rapids_tpu.conf import RapidsConf

REPO = str(pathlib.Path(__file__).resolve().parent.parent)


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------
def test_default_policy_is_power_of_two_ladder():
    """growth=2.0 / maxWasteFrac=0.5 must reproduce the original ladder
    bit-for-bit — existing deployments see identical shapes."""
    for base in (8, 256, 1024):
        for n in (1, base - 1, base, base + 1, 3 * base, 10_000):
            cap = base
            while cap < n:
                cap *= 2
            assert bucket_rows(n, base) == cap, (n, base)


def test_bucket_ladder_monotonic_and_covering():
    for pol in (BucketPolicy(1024, 2.0, 0.5), BucketPolicy(512, 2.0, 0.25),
                BucketPolicy(1024, 1.5, 0.5), BucketPolicy(64, 3.0, 0.2)):
        prev = 0
        for n in range(1, 50_000, 17):
            cap = pol.bucket(n)
            assert cap >= n, (pol, n, cap)
            assert cap >= prev, f"non-monotonic: {pol} {n}"
            prev = cap


def test_bucket_ladder_bounded_waste_and_shape_count():
    """Padding waste stays below growth*maxWasteFrac once past the floor,
    and the shape set stays logarithmic in the row range."""
    pol = BucketPolicy(min_rows=256, growth=2.0, max_waste_frac=0.25)
    caps = set()
    for n in range(257, 200_000, 13):
        cap = pol.bucket(n)
        caps.add(cap)
        waste = (cap - n) / cap
        assert waste < 2.0 * 0.25 + 1e-9, (n, cap, waste)
    # ~log2(200000/256) decades x at most 1/maxWasteFrac rungs each
    assert len(caps) <= 4 * 12, len(caps)


def test_bucket_conf_round_trip():
    """spark.rapids.tpu.shapeBuckets.* flows through configure_buckets
    into bucket_rows()/resolve_min_bucket(), and minRows=0 inherits
    batchRowsMinBucket."""
    try:
        configure_buckets(RapidsConf({
            "spark.rapids.tpu.shapeBuckets.minRows": 2048,
            "spark.rapids.tpu.shapeBuckets.growth": 1.5,
            "spark.rapids.tpu.shapeBuckets.maxWasteFrac": 0.25,
        }))
        pol = current_bucket_policy()
        assert (pol.min_rows, pol.growth, pol.max_waste_frac) \
            == (2048, 1.5, 0.25)
        assert resolve_min_bucket(None) == 2048
        assert bucket_rows(1) == 2048
        assert bucket_rows(1, 8) == 8          # explicit floor still wins
        # minRows=0 -> inherit the legacy batchRowsMinBucket key
        conf = RapidsConf({"spark.rapids.tpu.batchRowsMinBucket": 512})
        assert conf.min_bucket_rows == 512
        conf2 = RapidsConf({"spark.rapids.tpu.batchRowsMinBucket": 512,
                            "spark.rapids.tpu.shapeBuckets.minRows": 4096})
        assert conf2.min_bucket_rows == 4096
        with pytest.raises(ValueError):
            RapidsConf({"spark.rapids.tpu.shapeBuckets.growth": 1.0})
        with pytest.raises(ValueError):
            RapidsConf({"spark.rapids.tpu.shapeBuckets.maxWasteFrac": 0.0})
    finally:
        configure_buckets(RapidsConf())
    assert resolve_min_bucket(None) == 1024


# ---------------------------------------------------------------------------
# the AOT table of the mesh programs
# ---------------------------------------------------------------------------
def _compile_spans(tracer, program):
    return [e for e in tracer.events()
            if e.name == "compile" and e.args.get("program") == program]


@pytest.fixture
def traced():
    from spark_rapids_tpu.utils.tracing import get_tracer
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    tracer.clear()
    yield tracer
    tracer.enabled = was
    tracer.clear()


def _aot(key, built=None):
    """``aot_program`` of a one-line program under ``key``."""
    from spark_rapids_tpu.utils.compile_cache import aot_program, named_jit

    def build():
        if built is not None:
            built.append(key)
        return named_jit(lambda x: x + 1.0, "stage")
    return aot_program(key, build, (jnp.ones(8),), name="stage")


def test_aot_program_keeps_one_executable_a_key(traced):
    """The same key returns the same executable: ``build`` is not called
    and no second ``compile`` span is booked."""
    from spark_rapids_tpu.utils.compile_cache import clear_cache
    clear_cache()
    built = []
    prog, compiled = _aot(("k", 1), built)
    assert compiled and built == [("k", 1)]
    assert len(_compile_spans(traced, "srt_stage")) == 1
    again, compiled = _aot(("k", 1), built)
    assert again is prog and not compiled and built == [("k", 1)]
    assert len(_compile_spans(traced, "srt_stage")) == 1
    assert float(prog(jnp.ones(8))[0]) == 2.0
    other, compiled = _aot(("k", 2), built)
    assert compiled and other is not prog


def test_aot_program_is_a_bounded_lru():
    """64 executables are kept: the 65th key evicts the one used longest
    ago, which a hit in between moves to the young end."""
    from spark_rapids_tpu.utils import compile_cache as cc
    cc.clear_cache()
    for i in range(cc._AOT_MAX):
        _aot(("lru", i))
    assert len(cc._AOT) == cc._AOT_MAX == 64
    assert not _aot(("lru", 0))[1]              # a hit: 0 is young again
    assert _aot(("lru", 64))[1]                 # the 65th key
    assert len(cc._AOT) == 64
    assert ("stage", ("lru", 1)) not in cc._AOT
    assert ("stage", ("lru", 0)) in cc._AOT
    assert _aot(("lru", 1))[1]                  # evicted: compiled again


def test_clear_cache_empties_the_aot_table():
    from spark_rapids_tpu.utils import compile_cache as cc
    _aot(("clear", 0))
    assert cc._AOT
    cc.clear_cache()
    assert not cc._AOT
    assert _aot(("clear", 0))[1]


@pytest.mark.parametrize("program", ["mesh_stage", "ici_all_to_all"])
def test_the_mesh_programs_are_kept_in_the_one_aot_table(traced, program):
    """A group-by over a 4-virtual-device mesh compiles its all-to-all and
    its mesh stage into ``_AOT``; the same query again compiles neither."""
    from spark_rapids_tpu.expr.functions import col, sum as fsum
    from spark_rapids_tpu.parallel.mesh import data_parallel_mesh
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils import compile_cache as cc
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    cc.clear_cache()
    sess = TpuSession({"spark.rapids.tpu.batchRowsMinBucket": 8,
                       "spark.rapids.tpu.shuffle.partitions": 4,
                       "spark.rapids.sql.test.enabled": True,
                       # with AQE no mesh stage is planned
                       "spark.rapids.tpu.aqe.enabled": False})
    sess.attach_mesh(data_parallel_mesh(4))
    rng = np.random.default_rng(0)
    t = pa.table({"k": rng.integers(0, 40, 600),
                  "v": rng.uniform(0, 10, 600)})

    def query():
        df = sess.create_dataframe(t, num_partitions=3)
        return df.group_by("k").agg(fsum(col("v")).alias("s")).collect()

    try:
        first = query().to_pandas().sort_values("k")
        kept = [k for k in cc._AOT if k[0] == program]
        assert kept, list(cc._AOT)
        compiled = len(_compile_spans(traced, "srt_" + program))
        assert compiled == len(kept)
        again = query().to_pandas().sort_values("k")
        assert len(_compile_spans(traced, "srt_" + program)) == compiled
        assert [k for k in cc._AOT if k[0] == program] == kept
        assert again.s.tolist() == first.s.tolist()
    finally:
        sess.close()


# ---------------------------------------------------------------------------
# persistent tier helpers
# ---------------------------------------------------------------------------
def _reset_tier():
    from spark_rapids_tpu.utils.compile_cache import (clear_cache,
                                                      configure_compile_cache)
    configure_compile_cache(RapidsConf())
    clear_cache()


@pytest.fixture
def tier_reset():
    _reset_tier()
    yield
    _reset_tier()


# TPC-H Q6 in a fresh process, with XLA's own compile counters
# (jax.monitoring) around it: every compile request, and those of them the
# persistent cache served
_SCRIPT = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
cache_dir = sys.argv[1]
import jax
xla = {{"requests": 0, "hits": 0}}
def _duration(event, seconds, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        xla["requests"] += 1
def _event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        xla["hits"] += 1
jax.monitoring.register_event_duration_secs_listener(_duration)
jax.monitoring.register_event_listener(_event)
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.tools import tpch
from spark_rapids_tpu.utils.compile_cache import cache_stats

sess = TpuSession({{
    "spark.rapids.tpu.batchRowsMinBucket": 128,
    "spark.rapids.tpu.compile.cacheDir": cache_dir,
}})
lineitem = tpch.gen_lineitem(0.001, seed=0, rows=1500)
df = sess.create_dataframe(lineitem, num_partitions=1).cache()
q = tpch.q6({{"lineitem": df}})
res = q.collect(device=True)
out = {{"revenue": res.column("revenue")[0].as_py(), "stats": cache_stats(),
       "xla": xla}}
sess.close()
print("RESULT " + json.dumps(out))
"""


def _run_subprocess(cache_dir: str, **extra_env) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(repo=REPO), cache_dir],
        capture_output=True, text=True, timeout=480, env=env, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def _tier_files(tier) -> list:
    """What lies in an engine directory besides XLA's own ``xla/``."""
    return sorted(n for n in os.listdir(tier) if n != "xla")


def test_persistent_tier_zero_compiles_across_processes(tmp_path):
    """THE acceptance pin: a TPC-H query in a fresh process after a prior
    run compiles nothing — every compile request it makes is served from
    XLA's persistent cache — and answers the same."""
    cache_dir = str(tmp_path / "tier")
    cold = _run_subprocess(cache_dir)
    assert cold["stats"]["compiles"] > 0
    assert cold["xla"]["requests"] > cold["xla"]["hits"], cold["xla"]
    (tier,) = os.listdir(cache_dir)
    assert os.listdir(os.path.join(cache_dir, tier, "xla"))
    assert _tier_files(os.path.join(cache_dir, tier)) == []

    warm = _run_subprocess(cache_dir)
    assert warm["revenue"] == cold["revenue"]
    assert warm["xla"]["requests"] > 0
    assert warm["xla"]["hits"] == warm["xla"]["requests"], warm["xla"]
    assert _tier_files(os.path.join(cache_dir, tier)) == []


def test_external_cache_dir_holds_every_entry(tmp_path):
    """A fresh process with JAX_COMPILATION_CACHE_DIR set: XLA's entries
    land directly in that directory and nothing is written where the conf
    points."""
    ext, conf_dir = tmp_path / "placed", tmp_path / "conf"
    out = _run_subprocess(str(conf_dir), JAX_COMPILATION_CACHE_DIR=str(ext))
    assert out["stats"]["compiles"] > 0
    names = os.listdir(ext)
    assert [n for n in names if n != "srtpu"], names    # XLA executables
    assert os.listdir(ext / "srtpu") == []
    assert not conf_dir.exists()


def test_external_cache_dir_is_left_to_jax(tmp_path, tier_reset,
                                           monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: the engine
    never re-points jax_compilation_cache_dir (not when a conf dir is also
    given, not when a later session has the tier off), adds no fingerprint
    level, and keeps its own files in the fixed 'srtpu' subdirectory."""
    import jax as _jax
    from spark_rapids_tpu.utils.compile_cache import (cached_jit,
                                                      configure_compile_cache,
                                                      persistent_cache_dir)
    ext = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ext))
    # what jax's import would have read from the environment
    before = _jax.config.jax_compilation_cache_dir
    _jax.config.update("jax_compilation_cache_dir", str(ext))
    try:
        conf = RapidsConf(
            {"spark.rapids.tpu.compile.cacheDir": str(tmp_path / "conf")})
        assert configure_compile_cache(conf) == str(ext / "srtpu")
        assert _jax.config.jax_compilation_cache_dir == str(ext)
        cached_jit("test|external|v1", lambda: (lambda x: x * 3.0),
                   name="stage")(
            jnp.ones(8))
        assert "srtpu" in os.listdir(ext)
        assert not [n for n in os.listdir(ext)
                    if n.endswith(f"-jax{_jax.__version__}")]
        assert not (tmp_path / "conf").exists()
        # tier off in the next session: still not the engine's to un-wire
        assert configure_compile_cache(RapidsConf(
            {"spark.rapids.tpu.compile.enabled": False})) is None
        assert persistent_cache_dir() is None
        assert _jax.config.jax_compilation_cache_dir == str(ext)
    finally:
        _jax.config.update("jax_compilation_cache_dir", before)


def test_default_cache_path_is_identical_across_calls(tmp_path, tier_reset,
                                                     monkeypatch):
    """Unset environment: the tier directory is a pure function of the
    conf dir, this machine and the jax version — no pid, time or tempfile
    — so a second call (or process) finds the first one's cache."""
    import jax as _jax
    from spark_rapids_tpu.utils.compile_cache import (configure_compile_cache,
                                                      machine_fingerprint)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    conf = RapidsConf({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    first = configure_compile_cache(conf)
    xla_first = _jax.config.jax_compilation_cache_dir
    _reset_tier()
    assert _jax.config.jax_compilation_cache_dir is None
    assert configure_compile_cache(conf) == first
    assert _jax.config.jax_compilation_cache_dir == xla_first
    assert first == os.path.join(
        str(tmp_path), f"{machine_fingerprint()}-jax{_jax.__version__}")
    assert xla_first == os.path.join(first, "xla")


# ---------------------------------------------------------------------------
# no third tier: nothing of the manifest / export / warm-pool tier is left
# ---------------------------------------------------------------------------
def _q6(sess):
    from spark_rapids_tpu.tools import tpch
    lineitem = tpch.gen_lineitem(0.001, seed=0, rows=1500)
    df = sess.create_dataframe(lineitem, num_partitions=1)
    return tpch.q6({"lineitem": df}).collect(device=True)


@pytest.mark.parametrize("config", ["tpch-sf1-1chip", "tpch-sf1-mesh4"])
def test_the_benchmarks_session_conf_runs_and_close_writes_no_tier(
        tmp_path, tier_reset, monkeypatch, config):
    """The configuration files still carry
    ``spark.rapids.tpu.compile.warmPool.enabled``, now an unregistered key:
    a session opened with their ``session_conf`` as committed plans and
    runs Q6, and ``close()`` leaves no manifest, no exports and no
    warm-pool thread behind."""
    from spark_rapids_tpu.conf import conf_entries, import_conf_modules
    from spark_rapids_tpu.session import TpuSession
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        conf = dict(json.load(f)["session_conf"])
    stale = "spark.rapids.tpu.compile.warmPool.enabled"
    import_conf_modules()
    assert stale in conf and stale not in {e.key for e in conf_entries()}
    conf["spark.rapids.tpu.compile.cacheDir"] = str(tmp_path)
    sess = TpuSession(conf)
    try:
        assert sess.conf.get(stale) is False          # kept raw, read by none
        revenue = _q6(sess).column("revenue")[0].as_py()
    finally:
        sess.close()
    assert revenue > 0
    (tier,) = os.listdir(tmp_path)
    assert _tier_files(tmp_path / tier) == []
    assert not [t.name for t in threading.enumerate()
                if "warm-pool" in t.name]


@pytest.mark.parametrize("placed_by", ["conf", "environment"])
def test_an_older_versions_tier_files_are_left_alone(
        tmp_path, tier_reset, monkeypatch, capsys, placed_by):
    """A directory that holds an older version's ``manifest.json`` and
    ``exports/*.jaxexport`` (garbage in both) is opened, used and closed
    without a read of, a write to, or a warning about either."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils.compile_cache import machine_fingerprint
    if placed_by == "conf":
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        tier = tmp_path / f"{machine_fingerprint()}-jax{jax.__version__}"
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        tier = tmp_path / "srtpu"
    (tier / "exports").mkdir(parents=True)
    old = {tier / "manifest.json": b"{ this is not json",
           tier / "exports" / "bad.jaxexport": b"not a serialized export"}
    for path, data in old.items():
        path.write_bytes(data)
    stamps = {p: os.stat(p).st_mtime_ns for p in old}
    opened = []
    real_open = builtins.open

    def recording_open(file, *args, **kwargs):
        opened.append(str(file))
        return real_open(file, *args, **kwargs)
    monkeypatch.setattr(builtins, "open", recording_open)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sess = TpuSession({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
        try:
            assert _q6(sess).num_rows == 1
        finally:
            sess.close()
    monkeypatch.setattr(builtins, "open", real_open)
    assert not [p for p in opened
                if "manifest" in p or p.endswith(".jaxexport")], opened
    for path, data in old.items():
        assert path.read_bytes() == data
        assert os.stat(path).st_mtime_ns == stamps[path]
    assert sorted(os.listdir(tier / "exports")) == ["bad.jaxexport"]
    said = capsys.readouterr()
    for text in [said.out, said.err] + [str(w.message) for w in caught]:
        assert "manifest" not in text and "export" not in text, text
