"""From a profiler trace to the numbers the per-layer metrics read.

`load_xplane` reads JAX's `.xplane.pb` with `jax.profiler.ProfileData`
(a parser: it starts no backend) into a plain dict of events; `reduce`
turns that dict into device busy time, time by operation and by named
scope, collective time with the part of it that no compute hides, and the
longest idle gaps by what the host was doing. The dict form is also what
the recorded fixture under benchmark/fixtures/ holds, so `reduce` is
checked against a trace from the chip without the chip.

Events: {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
"async": [[name, start_ns, dur_ns], ...], "modules": [[name, start_ns,
dur_ns], ...]}}, "host": [[name, start_ns, dur_ns], ...]}. `name` is the HLO
instruction's name (`fusion.795`): the trace of this runtime carries the
instruction text and no name scope, so the scope of an operation (the
program's jax.named_scope path, and the Pallas kernel's name) comes from the
compiled step's own HLO metadata, as a {instruction name: op_name} map that
the child reads from the program it timed (`scopes`). Operations nest (a
`while` holds its body's operations): time by operation and by scope is
SELF time, and the device is busy while a leaf operation runs.

The interval arithmetic (merge overlapping intervals before summing; a
collective's exposed part is what no compute interval covers) follows the
program's telemetry/trace.py, which PERF.md lists for deletion once this
is the one copy.
"""

from __future__ import annotations

import re

COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "reduce-scatter",
                       "collective-permute", "all-to-all",
                       "collective-broadcast", "send", "recv")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PREFIX = "host/"


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def short_name(text: str) -> str:
    """`%fusion.795 = bf16[...] fusion(...)` -> `fusion.795`."""
    m = _INSTR.match(text)
    return m.group(1) if m else text[:64]


def scopes_from_hlo(text: str) -> dict:
    """{instruction name: op_name} of a compiled module's text: where in
    the program's name scopes each instruction comes from."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            if op:
                out[m.group(1)] = op.group(1)
    return out


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        dev["ops"].append([short_name(ev.name),
                                           int(ev.start_ns),
                                           int(ev.duration_ns)])
                elif line.name == "Async XLA Ops":
                    for ev in line.events:
                        name = short_name(ev.name)
                        if is_collective(name):
                            dev["async"].append([name, int(ev.start_ns),
                                                 int(ev.duration_ns)])
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        dev["modules"].append([ev.name, int(ev.start_ns),
                                               int(ev.duration_ns)])
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def merged(intervals: list) -> list:
    """Overlapping [start, end) intervals merged, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals: list) -> float:
    return float(sum(e - s for s, e in merged(intervals)))


def uncovered(intervals: list, cover: list) -> float:
    """Length of `intervals` (merged) that no interval of `cover` covers."""
    cover = merged(cover)
    left = 0.0
    j = 0
    for s, e in merged(intervals):
        pos = s
        while j < len(cover) and cover[j][1] <= pos:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > pos:
                left += cover[k][0] - pos
            pos = max(pos, cover[k][1])
            k += 1
        if pos < e:
            left += e - pos
    return left


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVE_PREFIXES)


def _step_window(dev: dict) -> tuple:
    """[start, end) of the whole step executions in one device's trace, and
    how many: the executions of the module that ran most (by time), first
    start to last end, leaving out one that the trace cut short at either
    end (an execution shorter than 0.8 of the median)."""
    by_name: dict = {}
    for name, start, dur in dev["modules"]:
        by_name.setdefault(name, []).append((start, dur))
    if not by_name:
        return None
    name = max(by_name, key=lambda n: sum(d for _, d in by_name[n]))
    runs = sorted(by_name[name])
    durs = sorted(d for _, d in runs)
    median = durs[len(durs) // 2]
    whole = [(s, d) for s, d in runs if d >= 0.8 * median]
    return whole[0][0], whole[-1][0] + whole[-1][1], len(whole), name


def self_times(ops: list) -> tuple:
    """(self time of each operation, whether it holds others): an
    operation's duration minus that of the operations directly inside it.
    A child of no duration (the zero-length `custom-call` markers the
    runtime puts inside big fusions) takes nothing off its parent and does
    not make it a holder: the parent is a leaf, and the device is busy
    while it runs."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [float(o[2]) for o in ops]
    holds = [False] * len(ops)
    stack: list = []
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and stack[-1][0] <= start:
            stack.pop()
        for parent_end, parent in reversed(stack):
            if end <= parent_end:       # the nearest operation that holds it
                own[parent] -= ops[i][2]
                if ops[i][2] > 0:
                    holds[parent] = True
                break
        stack.append((end, i))
    return own, holds


def cut(events: dict, max_ops: int = 4000) -> dict:
    """A copy small enough to keep as a fixture: the first whole step of
    each device, its first max_ops operations, and the host spans beside
    them."""
    out = {"devices": {}, "host": []}
    lo = hi = None
    for plane, dev in events["devices"].items():
        win = _step_window(dev)
        if win is None:
            continue
        name = win[3]
        first = sorted((s, d) for n, s, d in dev["modules"] if n == name
                       and s >= win[0])[0]
        s0 = first[0]
        ops = sorted((o for o in dev["ops"] if o[1] >= s0),
                     key=lambda o: o[1])[:max_ops]
        e0 = max(o[1] + o[2] for o in ops)
        ops = [o for o in ops if o[1] + o[2] <= e0]
        out["devices"][plane] = {
            "ops": ops,
            "async": [a for a in dev.get("async", [])
                      if s0 <= a[1] and a[1] + a[2] <= e0],
            "modules": [[name, s0, e0 - s0]]}
        lo = s0 if lo is None else min(lo, s0)
        hi = e0 if hi is None else max(hi, e0)
    if lo is not None:
        out["host"] = [h for h in events["host"]
                       if h[1] + h[2] >= lo and h[1] <= hi]
    if "scopes" in events:
        used = {o[0] for dev in out["devices"].values() for o in dev["ops"]}
        out["scopes"] = {k: v for k, v in events["scopes"].items()
                         if k in used}
    return out


def reduce(events: dict) -> dict:
    """Per-device averages over the whole steps of the trace."""
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    scopes = events.get("scopes", {})
    n = len(devices)
    acc = {"window_s": 0.0, "busy_s": 0.0, "collective_s": 0.0,
           "collective_exposed_s": 0.0, "steps": 0}
    by_op: dict = {}
    by_scope: dict = {}
    gaps: list = []
    first_plane = sorted(devices)[0]
    for plane in sorted(devices):
        dev = devices[plane]
        win = _step_window(dev)
        if win is None:
            raise ValueError(f"{plane}: no module executions in the trace")
        lo, hi, steps, _ = win
        ops = [o for o in dev["ops"] if o[1] >= lo and o[1] + o[2] <= hi]
        own, holds = self_times(ops)
        leaves = [o for o, h in zip(ops, holds) if not h]
        spans = [[o[1], o[1] + o[2]] for o in leaves]
        coll = [[o[1], o[1] + o[2]] for o in leaves if is_collective(o[0])]
        coll += [[a[1], a[1] + a[2]] for a in dev.get("async", [])
                 if a[1] >= lo and a[1] + a[2] <= hi]
        comp = [[o[1], o[1] + o[2]] for o in leaves
                if not is_collective(o[0])]
        acc["window_s"] += (hi - lo) / 1e9 / n
        acc["busy_s"] += total(spans) / 1e9 / n
        acc["collective_s"] += total(coll) / 1e9 / n
        acc["collective_exposed_s"] += uncovered(coll, comp) / 1e9 / n
        acc["steps"] = max(acc["steps"], steps)
        for (name, _, _), t in zip(ops, own):
            by_op[name] = by_op.get(name, 0.0) + t / 1e9 / n
            scope = scopes.get(name, "")
            by_scope[scope] = by_scope.get(scope, 0.0) + t / 1e9 / n
        if plane == first_plane:
            prev = lo
            for s, e in merged(spans):
                if s > prev:
                    gaps.append([prev, s])
                prev = max(prev, e)
            if hi > prev:
                gaps.append([prev, hi])
    # the idle gaps of the first device, by the host span that overlaps
    # each most
    host = sorted(events.get("host", []), key=lambda h: h[1])
    named: dict = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:2000]:
        best, best_len = "host/unattributed", 0
        for name, hs, hd in host:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best_len:
                best, best_len = name, ov
        named[best] = named.get(best, 0.0) + (e - s) / 1e9
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(named.items(), key=lambda kv: -kv[1])[:10]

    def label(name):
        scope = scopes.get(name, "")
        tail = "/".join(scope.split("/")[-3:]) if scope else ""
        return (f"{name} [{tail}]" if tail else name)[:200]

    acc.update({
        "by_op": by_op, "by_scope": by_scope,
        "breakdown": {"device_ops": [[label(k), v] for k, v in top_ops],
                      "idle_gaps": [[k, v] for k, v in top_gaps]},
    })
    return acc
