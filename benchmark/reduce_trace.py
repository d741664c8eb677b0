"""From a ``jax.profiler`` trace (xplane) to the numbers the per-layer
metrics and the ``breakdown`` read.

What it takes from the trace:

- host plane ``/host:CPU``: the ``bench.collect`` spans that ``run.py`` puts
  around each ``collect()`` (``jax.profiler.TraceAnnotation``);
- device planes ``/device:TPU:<n>``: line ``XLA Ops`` (every operation that
  ran, with start and duration), line ``XLA Modules`` (one event per
  executed program, named as XLA names it, e.g. ``jit_run(1234)``) and, for
  collectives that run asynchronously, line ``Async XLA Ops``.

The traced window is the first span's start to the last span's end. Busy
time of a device is the union of its operations' intervals clipped to that
window (operations nest and overlap: async copies, loop bodies), never their
sum. Works on ``jax.profiler.ProfileData`` or on anything shaped like it
(planes -> lines -> events with name, start_ns, duration_ns).
"""
import bisect
import glob
import os
import re
from collections import defaultdict

SPAN = "bench.collect"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # start..done of async copies and collectives
MODULES_LINE = "XLA Modules"
#: HLO operations that move data between chips
COLLECTIVE = re.compile(
    r"^%?(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"collective-broadcast|ragged-all-to-all|send|recv)\b")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _base_name(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """The trace names an operation by its whole HLO line, ``%fusion.3 =
    f32[...] fusion(...), kind=kLoop``: keep the result's name, and the
    custom-call target where there is one."""
    short = name.split(" = ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{short} {target.group(1)}" if target else short


def reduce_trace(profile, span: str = SPAN) -> dict:
    """All times in seconds. Keys a trace has nothing for are absent."""
    spans, devices = [], {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name == span]
        elif m:
            dev = devices.setdefault(
                int(m.group(1)), {OPS_LINE: [], ASYNC_LINE: [],
                                  MODULES_LINE: []})
            for line in plane.lines:
                if line.name in dev:
                    dev[line.name] += [(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
    spans.sort()
    out = {"queries": len(spans), "devices": len(devices)}
    if not spans:
        return out
    lo, hi = spans[0][0], spans[-1][1]
    ns = 1e-9
    out["window_s"] = (hi - lo) * ns
    out["span_s"] = total(spans) * ns
    if not devices:
        return out

    busy = {d: clip(union([(s, e) for _, s, e in v[OPS_LINE]]), lo, hi)
            for d, v in devices.items()}
    busy_s = {d: total(b) * ns for d, b in busy.items()}
    top = max(busy_s, key=busy_s.get)
    out["busy_s_by_device"] = [busy_s[d] for d in sorted(busy_s)]
    out["busy_s_mean"] = sum(busy_s.values()) / len(busy_s)
    out["busy_s_busiest"] = busy_s[top]
    out["busiest_device"] = top
    in_spans = sum(total(clip(busy[top], s, e)) for s, e in spans) * ns
    out["busy_in_spans_s"] = in_spans

    coll = [(s, e) for line in (OPS_LINE, ASYNC_LINE)
            for n, s, e in devices[top][line] if COLLECTIVE.match(n)]
    if coll:
        out["collective_s"] = total(clip(union(coll), lo, hi)) * ns

    by_module, by_op = defaultdict(float), defaultdict(float)
    for line, name_of, into in ((MODULES_LINE, _base_name, by_module),
                                (OPS_LINE, _op_name, by_op)):
        for n, s, e in devices[top][line]:
            into[name_of(n)] += max(0, min(e, hi) - max(s, lo)) * ns
    out["module_s"] = sorted(by_module.items(), key=lambda kv: -kv[1])
    out["op_s"] = sorted(by_op.items(), key=lambda kv: -kv[1])[:20]

    # idle gaps of the busiest device, labelled by whether a collect() was
    # in flight and by the programs on either side
    starts = [s for s, _ in spans]
    gaps = []
    edges = [lo] + [t for iv in busy[top] for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            i = bisect.bisect_right(starts, (a + b) / 2) - 1
            inside = i >= 0 and (a + b) / 2 < spans[i][1]
            gaps.append(((b - a) * ns, "inside collect" if inside
                         else "between collects", a, b))
    out["idle_s"] = {w: sum(g for g, x, _, _ in gaps if x == w)
                     for w in ("inside collect", "between collects")}
    mods = sorted((s, e, _base_name(n))
                  for n, s, e in devices[top][MODULES_LINE])
    out["longest_gaps"] = [
        (g, where,
         next((n for s, e, n in reversed(mods) if e <= a), "window start"),
         next((n for s, e, n in mods if s >= b), "window end"))
        for g, where, a, b in sorted(gaps, reverse=True)[:8]]
    return out


def breakdown(r: dict) -> dict:
    """The result line's ``breakdown``: at most ten entries a list."""
    if "module_s" not in r:
        return {}
    ops = [[f"module {n}", s] for n, s in r["module_s"][:4]]
    ops += [[f"op {n}", s] for n, s in r["op_s"][:10 - len(ops)]]
    gaps = [[f"all gaps {w}", s] for w, s in r["idle_s"].items()]
    gaps += [[f"{w}: after {b}, before {a}", g]
             for g, w, b, a in r["longest_gaps"]]
    return {"device_ops": ops[:10], "idle_gaps": gaps[:10]}
