"""From a ``jax.profiler`` trace (``.xplane.pb``) to the per-layer numbers.

What a TPU trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per program
execution) and ``XLA Ops`` (one event per HLO op, named by its full HLO text,
nested: a ``while`` spans its body's ops); and the plane ``/host:CPU`` whose
``python`` line carries the harness's ``TraceAnnotation`` spans.  All times
are nanoseconds on one clock.

    busy        union of the XLA Ops intervals (nesting cannot double-count)
    self time   an op's duration minus its children's; categories sum these
    round       an execution of the module that took the most device time
    idle gaps   the complement of busy inside the window, each attributed to
                the harness span that covers most of it
    window      from the first execution of the round program inside the
                harness's spans to the end of the last span
"""
from __future__ import annotations

import collections
import glob
import gzip
import os
import re

ANNOTATIONS = ("sample+args", "dispatch", "wait_round")
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "all-to-all",
               "reduce-scatter", "collective-broadcast")
_HEAD = re.compile(r"%(\S+) = ")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_KIND = re.compile(r"kind=k(\w+)")


def classify(hlo: str) -> tuple[str, str]:
    """(category, short op name) of one XLA Ops event from its HLO text."""
    head = _HEAD.match(hlo)
    name = head.group(1) if head else hlo[:40]
    rest = hlo[head.end():] if head else hlo
    depth = i = 0
    if rest.startswith("("):                  # tuple-shaped result: skip it
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    op = _OPCODE.search(rest, i)
    opcode = op.group(1) if op else "?"
    base = opcode.replace("-start", "").replace("-done", "")
    if base in COLLECTIVES:
        return "collective", name
    if base in ("while", "conditional", "call"):
        return "control flow", name
    if opcode == "fusion":
        if "copy" in name or "transpose" in name or "bitcast" in name:
            return "copy", name
        if "convolution" in name:
            return "matmul/conv fusion", name
        kind = _KIND.search(hlo)
        return {"Loop": "loop fusion", "Input": "reduce fusion",
                "Output": "matmul/conv fusion", "Convolution":
                "matmul/conv fusion", "Custom": "custom fusion"}.get(
                    kind.group(1) if kind else "", "fusion"), name
    if base in ("copy", "transpose", "reshape", "bitcast", "concatenate",
                "pad", "slice", "dynamic-slice", "dynamic-update-slice",
                "broadcast", "gather", "scatter", "convert"):
        return ("copy" if base in ("copy", "transpose") else "data movement"), name
    if base in ("convolution", "dot"):
        return "matmul/conv fusion", name
    return base, name


def merge(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events):
    """[(start, dur, name)] sorted by start -> list of self durations, in the
    same order: duration minus the time covered by nested events."""
    self_ns = [d for _, d, _ in events]
    stack = []                               # indices of open ancestors
    for i, (s, d, _) in enumerate(events):
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [max(v, 0.0) for v in self_ns]


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_dir(trace_dir: str, n_devices: int):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return reduce_file(found[-1], n_devices) if found else None


def reduce_file(path: str, n_devices: int):
    """The reduced trace, or None when no operation ran on a device (a CPU
    trace has no device plane)."""
    devices, spans = {}, []
    for plane in load(path).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < n_devices:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = {
                key: sorted((e.start_ns, e.duration_ns, e.name)
                            for e in lines[key].events) if key in lines else []
                for key in ("XLA Ops", "XLA Modules")}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in ln.events if e.name in ANNOTATIONS]
    if not devices or not any(d["XLA Ops"] for d in devices.values()):
        return None
    spans.sort()
    ops0 = devices[0]["XLA Ops"]
    # the traced window: the harness's own spans where the trace has them,
    # else the span of the device's ops ...
    if spans:
        w0, w1 = spans[0][0], max(e for _, e, _ in spans)
    else:
        w0, w1 = ops0[0][0], max(s + d for s, d, _ in ops0)
    # ... from the first execution of the round program (the module with the
    # most device time) inside it: before that the pipeline of rounds in
    # flight is filling, which a steady window never sees
    mods = collections.Counter()
    for s, d, name in devices[0]["XLA Modules"]:
        if s >= w0 and s + d <= w1:
            mods[name] += d
    program = mods.most_common(1)[0][0] if mods else None
    executions = [(s, d) for s, d, name in devices[0]["XLA Modules"]
                  if name == program and s >= w0 and s + d <= w1]
    if executions:
        w0 = executions[0][0]
    busy = {}
    for idx, dev in devices.items():
        busy[idx] = merge((max(s, w0), min(s + d, w1)) for s, d, _ in
                          dev["XLA Ops"] if s + d > w0 and s < w1)
    busy_ns = {i: sum(e - s for s, e in iv) for i, iv in busy.items()}

    # categories and op names by self time, device 0
    selfs = self_times(ops0)
    by_cat, by_op = collections.Counter(), collections.defaultdict(collections.Counter)
    for (s, d, hlo), own in zip(ops0, selfs):
        if s + d <= w0 or s >= w1:
            continue
        cat, name = classify(hlo)
        by_cat[cat] += own
        by_op[cat][name] += own
    top_cat = max((c for c in by_cat if c != "control flow"),
                  key=lambda c: by_cat[c], default=None)
    device_ops = [[c, v / 1e9] for c, v in by_cat.most_common(5)]
    if top_cat:
        device_ops += [[f"{top_cat}: {n}", v / 1e9]
                       for n, v in by_op[top_cat].most_common(10 - len(device_ops))]
    top_ops = sorted(((v, c, n) for c, ops in by_op.items() for n, v in ops.items()),
                     reverse=True)[:25]

    # device time inside each execution of the round program
    rounds = len(executions)
    round_busy = [sum(min(e, s + d) - max(b, s)
                      for b, e in busy[0] if e > s and b < s + d)
                  for s, d in executions]
    round_busy.sort()

    # idle gaps on device 0, by what the harness was doing
    gaps, edge = [], w0
    for b, e in busy[0] + [[w1, w1]]:
        if b > edge:
            cover = collections.Counter()
            for s0, s1, name in spans:
                if s1 > edge and s0 < b:
                    cover[name] += min(s1, b) - max(s0, edge)
            who, held = cover.most_common(1)[0] if cover else ("none", 0)
            gaps.append([who if held >= 0.5 * (b - edge) else "none",
                         (b - edge) / 1e9])
        edge = max(edge, e)
    gaps.sort(key=lambda g: -g[1])
    by_who = collections.Counter()
    for who, sec in gaps:
        by_who[who] += sec

    n = len(devices)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns.values()) / n / 1e9,
        "device0_busy_s": busy_ns[0] / 1e9,
        "rounds": rounds,
        "round_busy_ms": (round_busy[len(round_busy) // 2] / 1e6
                          if round_busy else None),
        "copy_s": by_cat.get("copy", 0) / 1e9,
        "collective_s": by_cat.get("collective", 0) / 1e9,
        "categories_s": {c: v / 1e9 for c, v in by_cat.items()},
        "idle_by_span_s": dict(by_who),
        "top_ops_s": [[f"{c}: {n}", v / 1e9] for v, c, n in top_ops],
        "breakdown": {"device_ops": device_ops[:10], "idle_gaps": gaps[:10]},
    }
