"""Compile-time amortization (ISSUE 7): canonical shape-bucket ladder,
persistent compile tier, and the warm-pool precompiler.

Covers the acceptance contract:
- bucket-ladder unit tests (monotonic, covering, bounded waste, conf
  round-trip through a session),
- persistent manifest + export save/load across a REAL subprocess
  boundary, pinning the zero-compiles-on-second-run criterion,
- corrupted-cache-dir tolerance (bad manifest, bad export file),
- warm pool precompiles-then-hits in-process,
- no-leaked-threads after session close.
"""
import json
import os
import pathlib
import subprocess
import sys
import threading

import jax.numpy as jnp
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
# persistent tier helpers
# ---------------------------------------------------------------------------
def _reset_tier():
    from spark_rapids_tpu.utils.compile_cache import (clear_cache,
                                                      configure_compile_cache,
                                                      stop_warm_pool)
    stop_warm_pool()
    configure_compile_cache(RapidsConf())
    clear_cache()


@pytest.fixture
def tier_reset():
    _reset_tier()
    yield
    _reset_tier()


# one tiny jitted computation exercised through cached_jit, signature-stable
_SCRIPT = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
cache_dir, phase = sys.argv[1], sys.argv[2]
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.tools import tpch
from spark_rapids_tpu.utils.compile_cache import cache_stats, warm_pool_wait

sess = TpuSession({{
    "spark.rapids.tpu.batchRowsMinBucket": 128,
    "spark.rapids.tpu.compile.cacheDir": cache_dir,
}})
if phase == "warm":
    assert warm_pool_wait(120), "warm pool did not settle"
lineitem = tpch.gen_lineitem(0.001, seed=0, rows=1500)
df = sess.create_dataframe(lineitem, num_partitions=1).cache()
q = tpch.q6({{"lineitem": df}})
res = q.collect(device=True)
out = {{"revenue": res.column("revenue")[0].as_py(), "stats": cache_stats()}}
sess.close()
print("RESULT " + json.dumps(out))
"""


def _run_subprocess(cache_dir: str, phase: str, **extra_env) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(repo=REPO), cache_dir, phase],
        capture_output=True, text=True, timeout=480, env=env, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_persistent_tier_zero_compiles_across_processes(tmp_path):
    """THE acceptance pin: a TPC-H query in a fresh process after a prior
    warmed run executes with compiles == 0 in cache_stats()."""
    cache_dir = str(tmp_path / "tier")
    cold = _run_subprocess(cache_dir, "cold")
    assert cold["stats"]["compiles"] > 0
    # the tier persisted a manifest with this process's signatures
    import glob
    manifests = glob.glob(os.path.join(cache_dir, "*", "manifest.json"))
    assert len(manifests) == 1
    with open(manifests[0]) as f:
        manifest = json.load(f)
    assert manifest["entries"]
    assert any(e["exports"] for e in manifest["entries"].values())
    exports = glob.glob(os.path.join(cache_dir, "*", "exports", "*"))
    assert exports

    warm = _run_subprocess(cache_dir, "warm")
    assert warm["revenue"] == pytest.approx(cold["revenue"], rel=1e-9)
    assert warm["stats"]["compiles"] == 0, warm["stats"]
    assert warm["stats"]["persist_warmed_entries"] > 0
    assert warm["stats"]["persist_hits"] > 0
    # cumulative cross-process hit counts merged on close
    with open(manifests[0]) as f:
        merged = json.load(f)
    assert sum(e["hits"] for e in merged["entries"].values()) \
        > sum(e["hits"] for e in manifest["entries"].values())


def test_external_cache_dir_holds_every_entry(tmp_path):
    """A fresh process with JAX_COMPILATION_CACHE_DIR set: XLA's entries
    land directly in that directory, the engine's under its 'srtpu'
    subdirectory, and nothing is written where the conf points."""
    ext, conf_dir = tmp_path / "placed", tmp_path / "conf"
    out = _run_subprocess(str(conf_dir), "cold",
                          JAX_COMPILATION_CACHE_DIR=str(ext))
    assert out["stats"]["compiles"] > 0
    names = os.listdir(ext)
    assert [n for n in names if n != "srtpu"], names    # XLA executables
    assert (ext / "srtpu" / "manifest.json").exists()
    assert os.listdir(ext / "srtpu" / "exports")
    assert not conf_dir.exists()


def test_warm_pool_precompiles_then_hits(tmp_path, tier_reset):
    """In-process round trip: session 1 compiles + persists; after a full
    cache clear, session 2's warm pool replays the export and the same
    signature dispatches with zero compiles."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils.compile_cache import (cache_stats,
                                                      cached_jit,
                                                      clear_cache,
                                                      warm_pool_wait)

    def builder():
        def fn(x):
            return (x * 2.0 + 1.0).sum()
        return fn

    x = jnp.arange(64, dtype=jnp.float32)
    sess1 = TpuSession(
        {"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    fn = cached_jit("test|warmpool|v1", builder, name="stage")
    expect = float(fn(x))
    assert cache_stats()["compiles"] == 1
    sess1.close()           # exports + manifest land on disk
    clear_cache()           # forget everything in-process

    sess2 = TpuSession(
        {"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    assert warm_pool_wait(60)
    stats = cache_stats()
    assert stats["persist_warmed_entries"] == 1, stats
    assert stats["persist_warm_compiles"] == 1
    fn2 = cached_jit("test|warmpool|v1", builder, name="stage")
    assert float(fn2(x)) == expect
    stats = cache_stats()
    assert stats["compiles"] == 0, stats
    assert stats["hits"] == 1
    assert stats["persist_hits"] == 1
    # an UNSEEN shape falls back to a live compile (counted), still correct
    y = jnp.arange(128, dtype=jnp.float32)
    assert float(fn2(y)) == float((y * 2.0 + 1.0).sum())
    stats = cache_stats()
    assert stats["compiles"] == 1
    assert stats["persist_misses"] == 1
    sess2.close()


def test_persist_merges_deltas_not_raw_totals(tmp_path, tier_reset):
    """A process cycling sessions (or a double close) must not re-merge
    counts it already persisted into the cumulative manifest."""
    import glob
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils.compile_cache import (cached_jit,
                                                      persist_compile_cache,
                                                      warm_pool_wait)

    def builder():
        return lambda x: x * 3.0

    x = jnp.ones(16)
    sess = TpuSession({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    cached_jit("test|delta|v1", builder, name="stage")(x)
    sess.close()

    def entry():
        (m,) = glob.glob(os.path.join(str(tmp_path), "*", "manifest.json"))
        with open(m) as f:
            return json.load(f)["entries"]["test|delta|v1"]

    assert (entry()["compiles"], entry()["hits"]) == (1, 0)
    persist_compile_cache()                   # double close: no growth
    assert (entry()["compiles"], entry()["hits"]) == (1, 0)
    # a second session in the SAME process adds only its own delta
    sess2 = TpuSession({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    warm_pool_wait(60)
    cached_jit("test|delta|v1", builder, name="stage")(x)   # in-process hit
    sess2.close()
    assert (entry()["compiles"], entry()["hits"]) == (1, 1)


def test_external_cache_dir_is_left_to_jax(tmp_path, tier_reset,
                                           monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: the engine
    never re-points jax_compilation_cache_dir (not when a conf dir is also
    given, not when a later session has the tier off), adds no fingerprint
    level, and keeps its own files in the fixed 'srtpu' subdirectory."""
    import jax as _jax
    from spark_rapids_tpu.utils.compile_cache import (cached_jit,
                                                      configure_compile_cache,
                                                      persist_compile_cache,
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
        persist_compile_cache()
        assert "srtpu" in os.listdir(ext)
        assert not [n for n in os.listdir(ext)
                    if n.endswith(f"-jax{_jax.__version__}")]
        assert (ext / "srtpu" / "manifest.json").exists()
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


def test_corrupted_manifest_is_dropped_not_fatal(tmp_path, tier_reset):
    from spark_rapids_tpu.utils.compile_cache import (cache_stats,
                                                      configure_compile_cache,
                                                      machine_fingerprint,
                                                      persistent_cache_dir)
    import jax as _jax
    tier = os.path.join(
        str(tmp_path), f"{machine_fingerprint()}-jax{_jax.__version__}")
    os.makedirs(tier, exist_ok=True)
    with open(os.path.join(tier, "manifest.json"), "w") as f:
        f.write("{ this is not json")
    conf = RapidsConf({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    assert configure_compile_cache(conf) == tier   # no raise
    assert persistent_cache_dir() == tier
    stats = cache_stats()
    assert stats["persist_dropped_entries"] == 1
    assert stats["persist_manifest_entries"] == 0


def test_corrupted_entries_and_exports_are_skipped(tmp_path, tier_reset):
    """A bad manifest entry is dropped entry-wise; a manifest pointing at
    a garbage export file makes the warm pool skip (warm_errors), never
    raise."""
    from spark_rapids_tpu.utils.compile_cache import (cache_stats,
                                                      configure_compile_cache,
                                                      machine_fingerprint,
                                                      warm_pool_wait)
    import jax as _jax
    tier = os.path.join(
        str(tmp_path), f"{machine_fingerprint()}-jax{_jax.__version__}")
    os.makedirs(os.path.join(tier, "exports"), exist_ok=True)
    with open(os.path.join(tier, "exports", "bad.jaxexport"), "wb") as f:
        f.write(b"definitely not a serialized export")
    manifest = {"version": 1, "entries": {
        "good|sig": {"hits": 5, "compiles": 1, "compile_s": 0.1,
                     "exports": [{"file": "bad.jaxexport",
                                  "aval_sig": "abc"}]},
        "bad-entry": {"hits": "NaN-ish"},
        "also-bad": ["not", "a", "dict"],
    }}
    with open(os.path.join(tier, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    conf = RapidsConf({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    configure_compile_cache(conf)
    assert warm_pool_wait(60)
    stats = cache_stats()
    assert stats["persist_manifest_entries"] == 1   # only the good entry
    assert stats["persist_dropped_entries"] == 2
    assert stats["persist_warm_errors"] == 1        # bad export skipped
    assert stats["persist_warmed_entries"] == 0


def test_no_leaked_warm_pool_threads(tmp_path, tier_reset):
    """Session close reaps the warm pool: no tpu-warm-pool* /
    warm-pool worker threads survive (no-leaked-threads contract)."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.utils.compile_cache import cached_jit, clear_cache

    def builder():
        return lambda x: x + 1.0

    sess = TpuSession({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    cached_jit("test|leak|v1", builder, name="stage")(jnp.ones(8))
    sess.close()
    clear_cache()
    sess2 = TpuSession({"spark.rapids.tpu.compile.cacheDir": str(tmp_path)})
    sess2.close()
    leaked = [t.name for t in threading.enumerate()
              if "warm-pool" in t.name and t.is_alive()]
    assert not leaked, leaked
