"""Turn a profiler trace (.xplane.pb) into numbers: the device's busy and idle
time, time and launches by operation name, time in Mosaic (Pallas) kernels and
in collectives, and the idle gaps by what the host was doing.

The trace is read with `jax.profiler.ProfileData` and nothing else.  A device
plane (`/device:TPU:<n>`) has a line `XLA Ops` whose events nest: a `while`
or `conditional` event covers the events of its body.  An operation "ran" only
in a LEAF event; a control-flow event's own time (what its children do not
cover) is sequencing between launches and counts as idle.  That is what makes
the idle share of one long fused program mean something: taken over all
events, a program that is one `while` loop would read 100% busy.
"""

import collections
import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # one event per asynchronous op, from its start to its done
WINDOW = "bench:window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|collective-broadcast")
# a Mosaic (Pallas) kernel is a custom call to this target; the event's name is
# the whole HLO instruction, which says so
MOSAIC = 'custom_call_target="tpu_custom_call"'
_HLO = re.compile(r"^%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Event = collections.namedtuple("Event", "name start end")
Trace = collections.namedtuple("Trace", "device overlay host")


@functools.lru_cache(maxsize=65536)  # a trace repeats a few thousand names 10^5 times
def op_label(name: str) -> tuple:
    """(label, opcode): what an operation is counted under, and what it is.
    The TPU trace names an event by its whole HLO instruction,
    `%copy.2191 = s32[16,10501024]{1,0:T(8,128)} copy(...)`.  The label is the
    instruction's name without XLA's instance number and the shape of its
    result without layouts, `copy s32[16,10501024]`, so that the 254 per-split
    copies of one matrix are one entry; the opcode tells a collective from its
    name (`%psum.3 = f32[...] all-reduce(...)` is labelled `psum f32[...]`).
    Any other name (`fusion.12`) loses its number only and is its own opcode."""
    m = _HLO.match(_LAYOUT.sub("", name))
    if m:
        return f"{re.sub(r'(\.\d+)+$', '', m.group(1))} {m.group(2)}"[:120], m.group(3)
    label = re.sub(r"(\.\d+)+$", "", name)
    return label, label


def find_xplane(trace_dir: str) -> str:
    """The one .xplane.pb that `jax.profiler.start_trace(trace_dir)` wrote."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line, keep=lambda name: True):
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events if keep(e.name)]


def _is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(op_label(name)[1]))


def read(path: str) -> Trace:
    """The trace's device operations and asynchronous collectives by chip, and
    the benchmark's own host annotations (`bench:*`); times in seconds."""
    from jax.profiler import ProfileData

    trace = Trace({}, {}, [])
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                trace.device[int(m.group(1))] = _events(line)
            elif m and line.name == ASYNC_LINE:
                trace.overlay[int(m.group(1))] = _events(line, _is_collective)
            elif plane.name.startswith("/host:"):
                trace.host.extend(_events(line, lambda name: name.startswith("bench:")))
    return trace


def leaves_and_self(events):
    """Split nested events: ([leaf events], {event index: self seconds}).
    Self time is an event's duration less what its direct children cover."""
    order = sorted(range(len(events)), key=lambda i: (events[i].start, -events[i].end))
    self_s = {i: events[i].end - events[i].start for i in order}
    is_parent = set()
    stack = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= events[stack[-1]].end:
            is_parent.add(stack[-1])
            self_s[stack[-1]] -= e.end - e.start
        stack.append(i)
    return [events[i] for i in order if i not in is_parent], self_s


def union(intervals):
    """Sorted disjoint [start, end] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _clip(events, lo, hi):
    return [e._replace(start=max(e.start, lo), end=min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def _overlap(a, b) -> float:
    """Total length of the intersection of two disjoint sorted interval lists."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def _innermost(spans, t: float) -> str:
    """Name of the host span open at time t that started last."""
    best = None
    for sp in spans:
        if sp.start <= t < sp.end and (best is None or sp.start >= best.start):
            best = sp
    return best.name if best else "(no host span)"


def reduce(trace: Trace, extra_host=()):
    """The numbers of one traced window, averaged over the chips traced.

    `extra_host` are further host spans on the trace's clock (the program's
    own, shifted by the caller).  The window is the `bench:window` annotation;
    without one it is the span of the device events.  Collective time is the
    union of the collective operations on the `XLA Ops` line (synchronous ones,
    and the start and done markers of asynchronous ones) and the start-to-done
    spans on the `Async XLA Ops` line; its exposed part is what no other leaf
    operation of that chip covers."""
    device, overlay, host = trace
    if not device:
        raise ValueError("the trace has no TPU device plane with an 'XLA Ops' line")
    win = [h for h in host if h.name == WINDOW]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        lo = min(e.start for evs in device.values() for e in evs)
        hi = max(e.end for evs in device.values() for e in evs)
    spans = [h for h in list(host) + list(extra_host) if h.name != WINDOW]
    n = len(device)
    out = {"window_s": hi - lo, "chips": n, "busy_s": 0.0, "launches": 0.0,
           "mosaic_s": 0.0, "collective_s": 0.0, "collective_exposed_s": 0.0}
    op_s = collections.Counter()
    leaf_s = collections.Counter()
    op_n = collections.Counter()
    gaps = collections.Counter()
    for chip, evs in device.items():
        evs = _clip(evs, lo, hi)
        leaves, self_s = leaves_and_self(evs)
        for i, s in self_s.items():
            op_s[op_label(evs[i].name)[0]] += s / n
        busy = union((e.start, e.end) for e in leaves)
        out["busy_s"] += _length(busy) / n
        out["launches"] += len(leaves) / n
        coll = [(e.start, e.end) for e in _clip(overlay.get(chip, ()), lo, hi)]
        compute = []
        for e in leaves:
            label, opcode = op_label(e.name)
            op_n[label] += 1.0 / n
            leaf_s[label] += (e.end - e.start) / n
            if COLLECTIVE.search(opcode):
                coll.append((e.start, e.end))
                continue
            compute.append((e.start, e.end))
            if MOSAIC in e.name:
                out["mosaic_s"] += (e.end - e.start) / n
        coll = union(coll)
        out["collective_s"] += _length(coll) / n
        out["collective_exposed_s"] += (_length(coll) - _overlap(coll, union(compute))) / n
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps[_innermost(spans, (g0 + g1) / 2)] += (g1 - g0) / n
    out["op_self_s"] = dict(op_s)  # control flow included, by its own time
    out["leaf_op_s"] = dict(leaf_s)
    out["op_launches"] = dict(op_n)
    out["idle_gaps_s"] = dict(gaps)
    return out


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The result line's optional `breakdown`."""
    def first(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": first(reduced["op_self_s"]), "idle_gaps": first(reduced["idle_gaps_s"])}
