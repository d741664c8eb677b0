"""srtpu-analyze static-analysis suite (spark_rapids_tpu/tools/analyze).

Covers the ISSUE 6 acceptance contract:
- fixture snippets trip each of the four checkers (sync / lock /
  thread / jit) and the known-clean variants stay clean,
- suppression syntax + baseline round-trip (sticky initial_inventory,
  regression detection on a seeded new violation),
- the tier-1 gate: the full package analyzes CLEAN against the
  committed baseline, a seeded violation in ANY checker category is
  flagged as new, and the host-sync baseline is strictly below the
  initial inventory (real fixes landed, not just suppressions).
"""
import json
import pathlib
import textwrap

import pytest

from spark_rapids_tpu.tools.analyze import (analyze_paths, baseline_summary,
                                            compare_to_baseline,
                                            default_baseline_path,
                                            load_baseline, severity_for,
                                            write_baseline)

PKG = pathlib.Path(__file__).resolve().parent.parent / "spark_rapids_tpu"


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def _rules(report, check=None):
    return sorted({f.rule for f in report.findings
                   if check is None or f.check == check})


# ---------------------------------------------------------------------------
# checker fixtures: each rule trips on a minimal snippet
# ---------------------------------------------------------------------------
def test_sync_checker_rules(tmp_path):
    path = _write(tmp_path, "sync_fixture.py", """\
        import numpy as np
        import jax
        import jax.numpy as jnp

        def hot_path(table, col):
            n = col.sum().item()
            host = np.asarray(col)
            got = jax.device_get(col)
            col.block_until_ready()
            rows = int(table.num_rows)
            total = int(jnp.sum(table.row_mask))
            return n, host, got, rows, total

        def fine(col, rows):
            dev = jnp.asarray(rows)        # stays on device: NOT a sync
            arr = np.array([1, 2, 3])      # host literal: NOT flagged
            return dev, arr, int(rows)     # plain int on host value
        """)
    report = analyze_paths([path], checks=["sync"])
    assert _rules(report) == ["sync-asarray", "sync-block-until-ready",
                              "sync-device-get", "sync-int-scalar",
                              "sync-item"]
    assert report.count("sync") == 6  # int() hits twice (num_rows + jnp)
    assert all(f.symbol == "hot_path" for f in report.findings)


def test_movement_unledgered_rule(tmp_path):
    """Direct device_get/.item() in a HOT package file that never talks
    to the movement ledger flags movement-unledgered; the same sync in a
    scope that notes the crossing (a funnel) is covered, and loose
    fixture files (hot by policy, no ledger obligation) never flag."""
    hot = tmp_path / "spark_rapids_tpu" / "exec"
    hot.mkdir(parents=True)
    (hot / "bypass.py").write_text(textwrap.dedent("""\
        import jax
        from ..utils import movement

        _SITE = "spark_rapids_tpu/exec/bypass.py::funnel"

        def funnel(col):
            t0 = movement.clock()
            host = jax.device_get(col)
            movement.note_d2h(_SITE, host.nbytes, t0)
            return host

        def bypass(col):
            return jax.device_get(col)

        def bypass_item(col):
            return col.sum().item()
        """))
    report = analyze_paths([str(tmp_path)], checks=["sync"])
    mv = [f for f in report.findings if f.rule == "movement-unledgered"]
    assert sorted(f.symbol for f in mv) == ["bypass", "bypass_item"]
    # the ledgered funnel still carries its plain sync finding, but no
    # movement-unledgered one
    assert not any(f.symbol == "funnel" for f in mv)
    # loose file outside the package tree: plain sync rules only
    loose = _write(tmp_path, "loose.py", """\
        import jax

        def f(col):
            return jax.device_get(col)
        """)
    loose_report = analyze_paths([loose], checks=["sync"])
    assert _rules(loose_report) == ["sync-device-get"]


def test_movement_unledgered_suppression(tmp_path):
    """sync-ok covers movement-unledgered too — one annotation per
    deliberate sync site, not one per rule."""
    hot = tmp_path / "spark_rapids_tpu" / "columnar"
    hot.mkdir(parents=True)
    (hot / "ok.py").write_text(
        "import jax\n\ndef f(col):\n"
        "    return jax.device_get(col)"
        "  # srtpu: sync-ok(cold scalar, once per query)\n")
    report = analyze_paths([str(tmp_path)], checks=["sync"])
    assert report.count("sync") == 0
    assert {f.rule for f in report.suppressed} \
        == {"sync-device-get", "movement-unledgered"}


def test_mesh_checker_rules(tmp_path):
    """mesh-shard-loop trips on a per-shard Python loop over the mesh
    extent in a hot exec scope; a scope that enters shard_map, a
    comprehension, and a mesh-ok'd site all stay clean."""
    hot = tmp_path / "spark_rapids_tpu" / "exec"
    hot.mkdir(parents=True)
    (hot / "serial.py").write_text(textwrap.dedent("""\
        def drain(node, mesh, axis):
            out = []
            for i in range(mesh.shape[axis]):
                out.append(node.dispatch(i))
            return out

        def drain_parts(node):
            n = node.num_partitions
            for p in range(n):
                node.dispatch(p)

        def spmd(mesh, cols, run):
            import jax
            for i in range(mesh.shape["dp"]):
                prime(i)   # spec plumbing around the collective: exempt
            return jax.shard_map(run, mesh=mesh, in_specs=None,
                                 out_specs=None)(cols)

        def alloc(node):
            return [[] for _ in range(node.num_partitions)]

        def ok(node):
            for p in range(node.num_partitions):  # srtpu: mesh-ok(input drain, not per-shard compute)
                node.pull(p)
        """))
    report = analyze_paths([str(tmp_path)], checks=["mesh"])
    assert _rules(report) == ["mesh-shard-loop"]
    assert sorted(f.symbol for f in report.findings) \
        == ["drain", "drain_parts"]
    assert [f.rule for f in report.suppressed] == ["mesh-shard-loop"]
    # outside the exec/shuffle packages the rule never fires
    loose = _write(tmp_path, "loose.py", """\
        def drain(node):
            for p in range(node.num_partitions):
                node.dispatch(p)
        """)
    assert analyze_paths([loose], checks=["mesh"]).count("mesh") == 0


def test_sync_checker_computed_receivers(tmp_path):
    """.item()/.block_until_ready() on computed expressions — the
    receiver has no qualifiable name but the sync is just as blocking."""
    path = _write(tmp_path, "computed.py", """\
        def f(a, b, mask, valid):
            n = (a - b).item()
            (mask & valid).block_until_ready()
            return n
        """)
    report = analyze_paths([path], checks=["sync"])
    assert _rules(report) == ["sync-block-until-ready", "sync-item"]


def test_sync_checker_skips_cold_packages(tmp_path):
    cold = tmp_path / "spark_rapids_tpu" / "tools"
    cold.mkdir(parents=True)
    (cold / "coldmod.py").write_text(
        "import numpy as np\n\ndef f(x):\n    return np.asarray(x)\n")
    report = analyze_paths([str(tmp_path)], checks=["sync"])
    assert report.count("sync") == 0
    assert severity_for(str(cold / "coldmod.py")) == "cold"
    assert severity_for(str(PKG / "exec" / "exchange.py")) == "hot"
    assert severity_for(str(PKG / "plan" / "aqe.py")) == "warm"


def test_lock_checker_deadlock_class(tmp_path):
    path = _write(tmp_path, "lock_fixture.py", """\
        class Node:
            def _materialize(self):
                with self._mat_lock:
                    self._materialize_locked()   # BAD: reaches semaphore

            def _materialize_locked(self):
                with self.sem.task_scope():
                    pass

        class GoodNode:
            def _materialize(self):
                with self._mat_lock:
                    with exempt_admission():
                        self._materialize_locked()

            def _materialize_locked(self):
                with self.sem.task_scope():
                    pass
        """)
    report = analyze_paths([path], checks=["lock"])
    hits = [f for f in report.findings
            if f.rule == "lock-sem-under-materialize"]
    assert len(hits) == 1
    assert hits[0].symbol == "Node._materialize"


def test_lock_checker_call_graph_is_transitive(tmp_path):
    path = _write(tmp_path, "lock_transitive.py", """\
        def leaf(sem):
            sem.acquire_if_necessary()

        def middle(sem):
            leaf(sem)

        def bad(self, sem):
            with self._mat_lock:
                middle(sem)

        def also_bad(self, sem):
            with self._mat_lock:
                run_tasks(middle)    # function reference, not a call
        """)
    report = analyze_paths([path], checks=["lock"])
    syms = sorted(f.symbol for f in report.findings
                  if f.rule == "lock-sem-under-materialize")
    assert syms == ["also_bad", "bad"]


def test_lock_checker_misuse_rules(tmp_path):
    path = _write(tmp_path, "lock_misuse.py", """\
        def bare(sem):
            sem.task_scope()          # never entered: does nothing

        def release_inside(sem):
            with sem.held():
                sem.release_all()     # drops the scope's own hold
        """)
    report = analyze_paths([path], checks=["lock"])
    assert _rules(report) == ["lock-bare-contextmanager",
                              "lock-release-all-in-scope"]


def test_thread_checker_rules(tmp_path):
    path = _write(tmp_path, "thread_fixture.py", """\
        import queue
        import threading
        import time
        from concurrent.futures import ThreadPoolExecutor

        q1 = queue.Queue()                       # unbounded
        q2 = queue.SimpleQueue()                 # unbounded by design
        q3 = queue.Queue(maxsize=4)              # fine
        q4 = queue.Queue(8)                      # fine (positional bound)
        t1 = threading.Thread(target=print)      # unnamed + non-daemon
        t2 = threading.Thread(target=print, name="x", daemon=True)  # fine
        p1 = ThreadPoolExecutor(max_workers=2)   # unnamed workers
        p2 = ThreadPoolExecutor(max_workers=2, thread_name_prefix="x")

        def poll():
            time.sleep(0.1)                      # engine sleep
        """)
    report = analyze_paths([path], checks=["thread"])
    rules = [f.rule for f in report.findings]
    assert rules.count("thread-unbounded-queue") == 2
    assert rules.count("thread-unnamed") == 2
    assert rules.count("thread-non-daemon") == 1
    assert rules.count("thread-sleep") == 1


def test_jit_checker_side_effects(tmp_path):
    path = _write(tmp_path, "jit_fixture.py", """\
        from spark_rapids_tpu.utils.compile_cache import cached_jit

        class Op:
            def batch_fn(self):
                conf_val = self.conf.get(KEY)     # build-time: fine
                def run(table):
                    print("tracing")              # BAD: effect in trace
                    self.metrics.add("rows", 1)   # BAD
                    return table.scale(conf_val)
                return run

            def execute(self):
                fn = cached_jit(self.plan_signature(), self.batch_fn)
                return fn
        """)
    report = analyze_paths([path], checks=["jit"])
    effects = [f for f in report.findings if f.rule == "jit-side-effect"]
    assert len(effects) == 2
    msgs = " ".join(f.message for f in effects)
    assert "print" in msgs and "metric registry" in msgs


def test_jit_checker_use_after_donate(tmp_path):
    path = _write(tmp_path, "donate_fixture.py", """\
        from spark_rapids_tpu.utils.compile_cache import cached_jit

        def bad(batch, build):
            fn = cached_jit("k|donate", build, donate_argnums=(0,))
            out = fn(batch)
            return batch.nbytes()     # BAD: donated buffers may be dead

        def good(batch, build):
            fn = cached_jit("k|donate", build, donate_argnums=(0,))
            size = batch.nbytes()     # before the call: fine
            if size:
                out = fn(batch)
            else:
                out = other(batch)    # sibling branch: fine
            return out
        """)
    report = analyze_paths([path], checks=["jit"])
    hits = [f for f in report.findings if f.rule == "jit-use-after-donate"]
    assert len(hits) == 1
    assert hits[0].symbol == "bad"


def test_jit_checker_donation_scopes_do_not_leak(tmp_path):
    """A donating call inside a nested def belongs to THAT scope: the
    outer function's same-named variable must not be flagged."""
    path = _write(tmp_path, "donate_nested.py", """\
        from spark_rapids_tpu.utils.compile_cache import cached_jit

        def outer(batch, build):
            def helper(batch):
                fn = cached_jit("k", build, donate_argnums=(0,))
                return fn(batch)
            out = helper(batch)
            return batch.nbytes()     # helper's param, not a donation
        """)
    report = analyze_paths([path], checks=["jit"])
    assert not [f for f in report.findings
                if f.rule == "jit-use-after-donate"]


def test_bucket_checker_rules(tmp_path):
    path = _write(tmp_path, "bucket_fixture.py", """\
        from spark_rapids_tpu.columnar.device import (DeviceTable,
                                                      bucket_rows,
                                                      resolve_min_bucket)

        def bad_call(n, host):
            cap = bucket_rows(n, 256)                     # literal floor
            t = DeviceTable.from_host(host, min_bucket=8)  # literal kw
            return cap, t

        class BadNode:
            def __init__(self, child, min_bucket: int = 1024):  # ad-hoc
                self.min_bucket = min_bucket

        class GoodNode:
            def __init__(self, child, min_bucket=None):
                self.min_bucket = resolve_min_bucket(min_bucket)

        def good_call(n, conf, host):
            cap = bucket_rows(n)                      # policy default
            cap2 = bucket_rows(n, conf.min_bucket_rows)  # conf-threaded
            return cap, cap2, DeviceTable.from_host(host)
        """)
    report = analyze_paths([path], checks=["bucket"])
    rules = [f.rule for f in report.findings]
    assert rules.count("bucket-literal") == 2
    assert rules.count("bucket-adhoc-default") == 1
    syms = {f.symbol for f in report.findings}
    assert syms == {"bad_call", "BadNode.__init__"}


def test_trace_checker_rules(tmp_path):
    path = _write(tmp_path, "trace_fixture.py", """\
        from spark_rapids_tpu.utils.tracing import get_tracer

        class Cluster:
            def _submit(self, w, envelope):
                self._task_qs[w].put(envelope)        # the chokepoint

            def sneaky(self, w, envelope):
                self._task_qs[w].put(envelope)        # bypasses _submit

            def sentinel(self, w):
                self._task_qs[w].put(None)  # srtpu: trace-ok(shutdown)

        def good(host):
            with get_tracer().span("upload", "upload"):
                return host

        def bad(tracer):
            tracer.span("upload", "upload")           # bare call: no-op

        def not_a_tracer(df):
            return df.span("2020", "2021")            # unrelated .span
        """)
    report = analyze_paths([path], checks=["trace"])
    rules = [f.rule for f in report.findings]
    assert rules.count("trace-span-no-with") == 1
    assert rules.count("trace-ctx-bypass") == 1
    assert {f.symbol for f in report.findings} == {"Cluster.sneaky", "bad"}
    assert len(report.suppressed) == 1


def test_memtrack_checker_rules(tmp_path):
    path = _write(tmp_path, "memtrack_fixture.py", """\
        from spark_rapids_tpu.columnar import DeviceTable

        def leaky(host):
            return DeviceTable.from_host(host, min_bucket=8)

        def accounted(host, catalog):
            t = DeviceTable.from_host(host, min_bucket=8)
            return catalog.register(t)

        def closure_accounted(host, catalog):
            def upload():
                return DeviceTable.from_host(host, min_bucket=8)
            return catalog.register(upload())

        def helper(host):
            return DeviceTable.from_host(host, 8)  # srtpu: memtrack-ok(caller registers)

        def derived(cols, mask):
            return DeviceTable(cols, mask)          # view: no new HBM
        """)
    report = analyze_paths([path], checks=["memtrack"])
    assert [f.rule for f in report.findings] == \
        ["memtrack-unregistered-upload"]
    assert {f.symbol for f in report.findings} == {"leaky"}
    assert len(report.suppressed) == 1


def test_retry_checker_rules(tmp_path):
    path = _write(tmp_path, "retry_fixture.py", """\
        from spark_rapids_tpu.columnar import DeviceTable
        from spark_rapids_tpu.memory.retry import (split_device_rows,
                                                   with_retry_split)
        from spark_rapids_tpu.utils.compile_cache import cached_jit

        def unguarded_dispatch(batch, build):
            fn = cached_jit('k', build)
            return fn(batch)

        def unguarded_upload(host):
            return DeviceTable.from_host(host, min_bucket=8)

        def guarded_dispatch(batch, build):
            fn = cached_jit('k', build)
            return with_retry_split(fn, batch,
                                    splitter=split_device_rows,
                                    scope='fixture')

        def guarded_closure(batch, build):
            fn = cached_jit('k', build)
            def dispatch(b):
                return fn(b)
            return with_retry_split(dispatch, batch,
                                    splitter=split_device_rows,
                                    scope='fixture')

        def merge_only(merged, build):
            fn = cached_jit('m', build)
            return fn(merged)  # srtpu: retry-ok(merge inputs cannot split)

        def plain_call(helper, batch):
            return helper(batch)   # not cached_jit-bound: never flagged
        """)
    report = analyze_paths([path], checks=["retry"])
    assert sorted(f.rule for f in report.findings) == [
        "retry-unguarded-dispatch", "retry-unguarded-upload"]
    assert {f.symbol for f in report.findings} == \
        {"unguarded_dispatch", "unguarded_upload"}
    assert len(report.suppressed) == 1


def test_retry_checker_skips_warm_packages(tmp_path):
    warm = tmp_path / "spark_rapids_tpu" / "parallel"
    warm.mkdir(parents=True)
    (warm / "warmmod.py").write_text(
        "from spark_rapids_tpu.columnar import DeviceTable\n\n"
        "def f(host):\n"
        "    return DeviceTable.from_host(host, min_bucket=8)\n")
    report = analyze_paths([str(tmp_path)], checks=["retry"])
    assert report.count("retry") == 0


def test_net_checker_rules(tmp_path):
    path = _write(tmp_path, "net_fixture.py", """\
        import socket

        def no_deadline(addr):
            s = socket.create_connection(addr)
            return s.recv(4)

        def with_deadline(addr):
            s = socket.create_connection(addr, timeout=5.0)
            s.settimeout(5.0)
            return s.recv(4)

        def positional_deadline(addr):
            with socket.create_connection(addr, 5.0) as s:
                return s.recv(4)

        def helper_recv(s):
            return s.recv(4)  # srtpu: net-ok(every caller sets the deadline before handing the socket here)

        def swallow(sock):
            try:
                sock.sendall(b"x")
            except Exception:
                pass

        def typed_handler(sock):
            try:
                sock.sendall(b"x")
            except OSError:
                return None
        """)
    report = analyze_paths([path], checks=["net"])
    assert sorted(f.rule for f in report.findings) == [
        "net-bare-except-pass", "net-connect-no-timeout",
        "net-socket-no-timeout"]
    assert {f.symbol for f in report.findings} == {"no_deadline", "swallow"}
    assert len(report.suppressed) == 1


def test_degrade_checker_rules(tmp_path):
    path = _write(tmp_path, "degrade_fixture.py", """\
        from spark_rapids_tpu.exec.fallback import (quarantine_on_failure,
                                                    with_host_fallback)
        from spark_rapids_tpu.memory.retry import (DeviceOomError,
                                                   with_retry_split)
        from spark_rapids_tpu.utils.compile_cache import cached_jit

        def unguarded(batch, build):
            fn = cached_jit('k', build)
            return fn(batch)

        def retry_guarded(batch, build):
            fn = cached_jit('k', build)
            return with_retry_split(fn, batch, scope='fixture')

        def fallback_guarded(node, batch, build, host_fn):
            fn = cached_jit('k', build)
            return with_host_fallback(node, fn, host_fn)(batch)

        def note_only_guarded(node, batch, build):
            fn = cached_jit('k', build)
            with quarantine_on_failure(node):
                return fn(batch)

        def swallows_everything(batch, fn):
            try:
                return fn(batch)
            except Exception:
                return None

        def swallows_structured(batch, fn):
            try:
                return fn(batch)
            except DeviceOomError:
                return None

        def reraises(batch, fn):
            try:
                return fn(batch)
            except Exception:
                raise

        def forwards(q, batch, fn):
            try:
                return fn(batch)
            except Exception:  # srtpu: degrade-ok(forwarded to the consumer queue)
                q.put(None)

        def typed_cleanup(handle):
            try:
                handle.close()
            except OSError:
                return None
        """)
    report = analyze_paths([path], checks=["degrade"])
    assert sorted(f.rule for f in report.findings) == [
        "degrade-swallowed-failure", "degrade-swallowed-failure",
        "degrade-unguarded-dispatch"]
    assert {f.symbol for f in report.findings} == \
        {"unguarded", "swallows_everything", "swallows_structured"}
    assert len(report.suppressed) == 1
    # the structured-error message names what was caught
    (structured,) = [f for f in report.findings
                     if f.symbol == "swallows_structured"]
    assert "DeviceOomError" in structured.message


def test_degrade_checker_skips_cold_packages(tmp_path):
    cold = tmp_path / "spark_rapids_tpu" / "tools"
    cold.mkdir(parents=True)
    (cold / "coldmod.py").write_text(
        "def f(x, fn):\n"
        "    try:\n"
        "        return fn(x)\n"
        "    except Exception:\n"
        "        return None\n")
    report = analyze_paths([str(tmp_path)], checks=["degrade"])
    assert report.count("degrade") == 0


def test_degrade_swallow_rule_covers_warm_packages(tmp_path):
    warm = tmp_path / "spark_rapids_tpu" / "parallel"
    warm.mkdir(parents=True)
    (warm / "warmmod.py").write_text(
        "from spark_rapids_tpu.utils.compile_cache import cached_jit\n\n"
        "def swallow(x, fn):\n"
        "    try:\n"
        "        return fn(x)\n"
        "    except Exception:\n"
        "        return None\n\n"
        "def dispatch(batch, build):\n"
        "    fn = cached_jit('k', build)\n"
        "    return fn(batch)\n")
    report = analyze_paths([str(tmp_path)], checks=["degrade"])
    # swallow rule reaches warm; the dispatch rule stays hot-only
    assert [f.rule for f in report.findings] == ["degrade-swallowed-failure"]


def test_net_checker_skips_cold_packages(tmp_path):
    cold = tmp_path / "spark_rapids_tpu" / "tools"
    cold.mkdir(parents=True)
    (cold / "coldnet.py").write_text(
        "import socket\n\ndef f(addr):\n"
        "    return socket.create_connection(addr)\n")
    report = analyze_paths([str(tmp_path)], checks=["net"])
    assert report.count("net") == 0


def test_bucket_checker_skips_cold_packages(tmp_path):
    cold = tmp_path / "spark_rapids_tpu" / "tools"
    cold.mkdir(parents=True)
    (cold / "coldmod.py").write_text(
        "def f(n):\n    return bucket_rows(n, 64)\n")
    report = analyze_paths([str(tmp_path)], checks=["bucket"])
    assert report.count("bucket") == 0


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------
def test_suppression_same_line_and_standalone(tmp_path):
    path = _write(tmp_path, "supp.py", """\
        import numpy as np

        def f(col):
            a = np.asarray(col)  # srtpu: sync-ok(host-only helper)
            # srtpu: sync-ok(cold error path)
            b = np.asarray(col)
            c = np.asarray(col)
            return a, b, c
        """)
    report = analyze_paths([path], checks=["sync"])
    assert report.count("sync") == 1          # only the unsuppressed one
    assert len(report.suppressed) == 2
    assert {f.line for f in report.findings} == {7}


def test_suppression_requires_reason(tmp_path):
    path = _write(tmp_path, "supp_empty.py", """\
        import numpy as np

        def f(col):
            return np.asarray(col)  # srtpu: sync-ok()
        """)
    report = analyze_paths([path], checks=["sync"])
    # empty reason: suppression inert AND reported as a meta finding
    assert report.count("sync") == 1
    assert any(f.rule == "meta-empty-suppression-reason"
               for f in report.findings)


def test_suppression_is_check_scoped(tmp_path):
    path = _write(tmp_path, "supp_scope.py", """\
        import queue

        q = queue.Queue()  # srtpu: sync-ok(wrong check name)
        """)
    report = analyze_paths([path], checks=["thread"])
    assert report.count("thread") == 1        # sync-ok does not cover it


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------
def test_baseline_roundtrip_and_regression(tmp_path):
    src = _write(tmp_path, "base.py", """\
        import numpy as np

        def f(col):
            return np.asarray(col)
        """)
    report = analyze_paths([src], checks=["sync"])
    assert report.count("sync") == 1
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(report, bl_path)
    # clean against its own baseline
    assert compare_to_baseline(report, load_baseline(bl_path)) == []
    # a second occurrence in the SAME function is a new violation
    pathlib.Path(src).write_text(pathlib.Path(src).read_text().replace(
        "return np.asarray(col)",
        "x = np.asarray(col)\n    return np.asarray(x)"))
    grown = analyze_paths([src], checks=["sync"])
    regs = compare_to_baseline(grown, load_baseline(bl_path))
    assert len(regs) == 1 and regs[0].rule == "sync-asarray"
    # initial_inventory is sticky across regeneration
    first = load_baseline(bl_path)["initial_inventory"]
    write_baseline(grown, bl_path)
    again = load_baseline(bl_path)
    assert again["initial_inventory"] == first
    assert again["counts"][regs[0].key()]["count"] == 2


def test_baseline_key_survives_line_drift(tmp_path):
    src = _write(tmp_path, "drift.py", """\
        import numpy as np

        def f(col):
            return np.asarray(col)
        """)
    report = analyze_paths([src], checks=["sync"])
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(report, bl_path)
    # unrelated code above shifts the line; the key (path+rule+symbol)
    # still matches, so no new violation is reported
    pathlib.Path(src).write_text(
        "import numpy as np\n\nPAD = 1\nPAD2 = 2\n\n\ndef f(col):\n"
        "    return np.asarray(col)\n")
    drifted = analyze_paths([src], checks=["sync"])
    assert compare_to_baseline(drifted, load_baseline(bl_path)) == []


# ---------------------------------------------------------------------------
# tier-1 gate: the package is clean vs the committed baseline
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def package_report():
    return analyze_paths([str(PKG)])


def test_tier1_package_clean_vs_committed_baseline(package_report):
    baseline = load_baseline(default_baseline_path())
    regressions = compare_to_baseline(package_report, baseline)
    assert not regressions, (
        "NEW static-analysis violation(s) — fix the site, suppress with "
        "'# srtpu: <check>-ok(reason)', or (for accepted debt) regenerate "
        "via python -m spark_rapids_tpu.tools.analyze --write-baseline:\n"
        + "\n".join(f.render() for f in regressions))


def test_tier1_seeded_violation_fails_each_category(tmp_path,
                                                    package_report):
    """A new violation in ANY checker category must be flagged as new
    against the committed baseline. The seeded file's keys are absent
    from the baseline, so analyzing it alone yields exactly the delta —
    the package-matches-baseline half is pinned by the tier-1 gate tests
    above, which lets this loop skip nine full-package re-scans."""
    seeds = {
        "sync": "import numpy as np\n\ndef f(c):\n"
                "    return np.asarray(c)\n",
        "lock": "def f(self, sem):\n    with self._mat_lock:\n"
                "        with sem.task_scope():\n            pass\n",
        "thread": "import queue\n\nq = queue.Queue()\n",
        "jit": "from spark_rapids_tpu.utils.compile_cache import "
               "cached_jit\n\ndef f(x, build):\n"
               "    fn = cached_jit('k', build, donate_argnums=(0,))\n"
               "    out = fn(x)\n    return x.sum()\n",
        "bucket": "from spark_rapids_tpu.columnar.device import "
                  "bucket_rows\n\ndef f(n):\n"
                  "    return bucket_rows(n, 512)\n",
        "trace": "def f(tracer):\n"
                 "    tracer.span('q', 'query')\n    return 1\n",
        "memtrack": "from spark_rapids_tpu.columnar import DeviceTable\n\n"
                    "def f(host):\n"
                    "    return DeviceTable.from_host(host, min_bucket=8)\n",
        "net": "def f(sock):\n    try:\n        sock.sendall(b'x')\n"
               "    except Exception:\n        pass\n",
        "retry": "from spark_rapids_tpu.utils.compile_cache import "
                 "cached_jit\n\ndef f(x, build):\n"
                 "    fn = cached_jit('k', build)\n"
                 "    return fn(x)\n",
    }
    baseline = load_baseline(default_baseline_path())
    for check, body in seeds.items():
        seeded_file = _write(tmp_path, f"seed_{check}.py", body)
        report = analyze_paths([seeded_file], checks=[check])
        regs = compare_to_baseline(report, baseline)
        assert regs and all(f.check == check for f in regs), \
            f"seeded {check} violation not detected"
        pathlib.Path(seeded_file).unlink()


def test_tier1_sync_debt_strictly_below_initial_inventory(package_report):
    """The acceptance criterion that forbids pure baselining: the live
    sync count must be strictly below the initial (pre-fix) inventory
    recorded when the analyzer first ran (137 sites)."""
    baseline = load_baseline(default_baseline_path())
    initial = baseline["initial_inventory"]["sync"]
    assert package_report.count("sync") < initial
    assert baseline["summary"]["checks"]["sync"]["total"] < initial


def test_tier1_thread_and_lock_and_jit_clean(package_report):
    """Conventions the engine already follows stay absolutely clean —
    these checks carry no baseline allowance at all."""
    assert package_report.count("thread") == 0
    assert package_report.count("lock") == 0
    assert package_report.count("jit") == 0
    assert package_report.count("meta") == 0
    # the shape-bucket policy refactor drove literal floors out of the
    # engine; the only survivors are reasoned bucket-ok suppressions
    # (cross-process wire-protocol constants)
    assert package_report.count("bucket") == 0
    # the trace-context contract is enforced from day one: every span is
    # with-scoped and every envelope goes through _submit (the one
    # shutdown-sentinel put carries a reasoned trace-ok suppression)
    assert package_report.count("trace") == 0


def test_baseline_summary_matches_committed_file(package_report):
    """baseline_summary() must agree with a live analyzer run so the
    trajectory metric is honest."""
    info = baseline_summary()
    assert info, "committed baseline missing"
    live = package_report.summary()["checks"].get("sync", {})
    committed = info["summary"]["checks"].get("sync", {})
    assert committed == live, (
        "committed baseline is stale — regenerate with "
        "python -m spark_rapids_tpu.tools.analyze --write-baseline")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_json_and_exit_codes(tmp_path, capsys):
    from spark_rapids_tpu.tools.analyze.__main__ import main

    src = _write(tmp_path, "climod.py",
                 "import numpy as np\n\ndef f(c):\n"
                 "    return np.asarray(c)\n")
    bl = str(tmp_path / "bl.json")
    # no baseline yet -> exit 2
    assert main([src, "--baseline", bl]) == 2
    capsys.readouterr()
    assert main([src, "--baseline", bl, "--write-baseline"]) == 0
    assert main([src, "--baseline", bl]) == 0
    out = capsys.readouterr().out
    assert "clean vs baseline" in out
    # grow a violation -> exit 1
    pathlib.Path(src).write_text(
        "import numpy as np\n\ndef f(c):\n"
        "    a = np.asarray(c)\n    return np.asarray(a)\n")
    assert main([src, "--baseline", bl]) == 1
    capsys.readouterr()
    # JSON mode round-trips
    assert main([src, "--baseline", bl, "--json", "--no-baseline"]) == 0
    obj = json.loads(capsys.readouterr().out)
    assert obj["summary"]["checks"]["sync"]["total"] == 2


def test_diagnose_renders_sync_debt(tmp_path):
    """tools/diagnose.py cross-references the committed baseline."""
    from spark_rapids_tpu.tools.diagnose import diagnose_path

    records = [
        {"event": "app_start", "app_id": "a", "schema_version": 3,
         "ts": 0.0, "conf": {}},
        {"event": "query_start", "query_id": 1, "ts": 0.0, "plan": "p"},
        {"event": "query_end", "query_id": 1, "ts": 1.0, "wall_s": 1.0,
         "final_plan": "p", "aqe_events": [], "spill_count": {},
         "semaphore_wait_s": 0.0, "stats": {}},
        {"event": "app_end", "ts": 1.0},
    ]
    p = tmp_path / "log.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    rep = diagnose_path(str(p))
    text = rep.summary()
    assert "static sync-site debt" in text
    assert "initial inventory 137" in text
    obj = json.loads(rep.to_json())
    assert obj["sync_debt"]["initial_inventory"]["sync"] == 137
