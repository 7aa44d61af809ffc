"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``: planes, their lines and events with
a start and a duration in nanoseconds. A device plane is one whose name
starts with ``/device:``; its operations are the events of its ``XLA Ops``
line. Host spans are events of any host plane's lines whose names the
harness gave them with ``jax.profiler.TraceAnnotation``.

On a TPU an operation's event is named by its whole HLO instruction
(``%wire_encode.3 = u8[...] custom-call(...)``); the operation's name is
the instruction's name before `` = ``. Operations nest: a ``while`` spans
the operations of its body, so an operation's own time is its duration
less that of the operations inside it. Asynchronous operations are spans
on the ``Async XLA Ops`` line.

The window is the host span named ``WINDOW``. Per device, busy time is the
union of the operation intervals inside it, and an idle gap is a stretch
of the window with no operation, named by the host span that overlaps it
most (``untracked`` where none does). Collective time is the union of the
XLA collective operations, asynchronous ones from start to done; the
codec and RDMA kernels are the Pallas kernels by name.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all[-_]reduce|all[-_]gather|all[-_]to[-_]all|reduce[-_]scatter"
    r"|collective[-_]permute|send|recv)([-_.]|$)")
# the opcode of an HLO instruction: the word before "(" after its shape
OPCODE = re.compile(r" = .*?[}\])] ([a-z][a-z0-9-]*)\(")
CODEC = re.compile(r"^wire_(encode|decode)")
RDMA = re.compile(r"^rdma_")
SUFFIX = re.compile(r"\.\d+$")

Interval = Tuple[int, int]


def opcode(event: str) -> str:
    """``%x.1 = u8[4]{0} all-to-all(...)`` -> ``all-to-all``; the name
    itself where the event is not an HLO instruction."""
    m = OPCODE.search(event)
    return m.group(1) if m else op_name(event)


def op_name(event: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``; a plain name
    stays as it is."""
    if event.startswith("%"):
        end = event.find(" = ")
        return event[1:end] if end > 0 else event[1:]
    return event


def base_name(name: str) -> str:
    """HLO instruction name without its ``.N`` suffix."""
    return SUFFIX.sub("", name)


def self_times(ops) -> Dict[str, int]:
    """Own time of each operation name: its events' durations less those
    of the events nested inside them on the same line."""
    own: Dict[str, int] = defaultdict(int)
    stack: List[Tuple[str, int]] = []
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        own[n] += e - s
        if stack and e <= stack[-1][1]:       # wholly inside its parent
            own[stack[-1][0]] -= e - s
        stack.append((n, e))
    return own


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers see. Times are seconds; lists run over
    the devices, in plane order."""
    window_s: float
    devices: List[str]
    busy_s: List[float]
    op_s: Dict[str, float]              # op name -> mean own seconds
    op_count: Dict[str, float]          # base name -> mean calls
    collective_s: List[float]
    codec_s: List[float]                # summed codec kernel time
    tp_site_s: List[float]              # union: collectives + codec + rdma
    idle_gaps: List[Tuple[str, float]]  # longest first, all devices;
    # gaps shorter than ``min_gap_ns`` count as idle but are not listed

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def mean(self, per_device: Sequence[float]) -> float:
        return sum(per_device) / len(per_device)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def _ops(line):
    """(name, opcode, start, end) of each operation on a device line."""
    for text, s, e in _events(line):
        yield op_name(text), opcode(text), s, e


def _module_of(mods, starts, s: int) -> str:
    """The program (``jit_step(1669...)``, its id cut to 6 digits) whose
    execution holds time ``s``; "" outside any."""
    i = bisect.bisect_right(starts, s) - 1
    if i < 0 or s >= mods[i][2]:
        return ""
    name, _, pid = mods[i][0].partition("(")
    return f"{name}({pid[:6]})" if pid else name


def _span_at(span_iv, starts, a: int, b: int) -> str:
    """The host span overlapping [a, b) most ("untracked" for none)."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    best, name = 0, "untracked"
    while i < len(span_iv) and span_iv[i][0][0] < b:
        ov = overlap(span_iv[i][0], (a, b))
        if ov > best:
            best, name = ov, span_iv[i][1]
        i += 1
    return name


def reduce(pd, spans: Sequence[str] = (), window: str = WINDOW,
           ops_line: str = OPS_LINE, min_gap_ns: int = 1000) -> Reduced:
    dev_ops: Dict[str, list] = {}
    dev_async: Dict[str, list] = defaultdict(list)
    dev_mods: Dict[str, list] = defaultdict(list)
    host: Dict[str, List[Interval]] = defaultdict(list)
    wanted = set(spans) | {window}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == ops_line:
                    dev_ops[plane.name] = list(_ops(line))
                elif line.name == ASYNC_LINE:
                    dev_async[plane.name] = list(_ops(line))
                elif line.name == MODULES_LINE:
                    dev_mods[plane.name] = sorted(
                        _events(line), key=lambda m: m[1])
        else:
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name in wanted:
                        host[name].append((s, e))
    devices = sorted(d for d, ops in dev_ops.items() if ops)
    if not devices:
        raise ValueError("the trace holds no device operations")
    if host.get(window):
        lo = min(s for s, _ in host[window])
        hi = max(e for _, e in host[window])
    else:
        lo = min(o[2] for d in devices for o in dev_ops[d])
        hi = max(o[3] for d in devices for o in dev_ops[d])
    span_iv = sorted(((iv, name) for name in spans
                      for iv in host.get(name, [])))
    starts = [iv[0] for iv, _ in span_iv]

    busy, coll, codec, site = [], [], [], []
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    nd = len(devices)
    for d in devices:
        ops = [o for o in dev_ops[d] if o[3] > lo and o[2] < hi]
        u = union(clip(((s, e) for _, _, s, e in ops), lo, hi))
        busy.append(total(u) / 1e9)
        # an operation is named within its program: names repeat across
        # the programs (one per compiled shape) of a run
        mods = dev_mods.get(d, [])
        mstarts = [m[1] for m in mods]
        clipped = [((_module_of(mods, mstarts, s) + "/" + n).lstrip("/"),
                    max(s, lo), min(e, hi)) for n, _, s, e in ops]
        for n, t in self_times(clipped).items():
            op_s[n] += t / 1e9 / nd
        for n, _, _, _ in ops:
            op_n[base_name(n)] += 1.0 / nd
        coll_iv = [(s, e) for n, code, s, e in ops + dev_async.get(d, [])
                   if COLLECTIVE.match(code)]
        codec_iv = [(s, e) for n, _, s, e in ops if CODEC.match(n)]
        rdma_iv = [(s, e) for n, _, s, e in ops if RDMA.match(n)]
        coll.append(total(union(clip(coll_iv, lo, hi))) / 1e9)
        codec.append(sum(e - s for s, e in clip(codec_iv, lo, hi)) / 1e9)
        site.append(total(union(clip(coll_iv + codec_iv + rdma_iv, lo,
                                     hi))) / 1e9)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= min_gap_ns:
                gaps.append((_span_at(span_iv, starts, a, b), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) / 1e9, devices=devices, busy_s=busy,
                   op_s=dict(op_s), op_count=dict(op_n), collective_s=coll,
                   codec_s=codec, tp_site_s=site, idle_gaps=gaps)
