"""Reduce a profiler trace to the numbers the per-layer metrics read.

A TPU trace (``jax.profiler``'s ``.xplane.pb``) holds, per chip, a plane
``/device:TPU:<i>`` whose line ``XLA Ops`` has one event per executed HLO
instruction (its name is the instruction's text, ``%name = ...``) and whose
line ``XLA Modules`` has one event per executed program. The host plane
``/host:CPU`` has a line ``python`` with the harness's own spans
(``jax.profiler.TraceAnnotation``, names starting ``bench.``). Timestamps of
both are nanoseconds on one clock.

Named scopes (``jax.named_scope``) do not reach the trace; they reach the
compiled program's HLO, where every instruction carries
``metadata={op_name="jit(f)/scope/..."}``. :func:`scope_map` reads that
text, and the reduction joins the two by instruction name within a module.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

HOST_PREFIX = "bench."

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


@dataclasses.dataclass
class Op:
    device: int
    module: str      # program name, e.g. "jit_step"
    name: str        # instruction name, e.g. "fusion.12"
    start: float     # ns
    dur: float       # ns


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float


@dataclasses.dataclass
class Trace:
    ops: list[Op]
    spans: list[Span]
    devices: int


def _module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into device ops and harness host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: list[Op] = []
    spans: list[Span] = []
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            devices += 1
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _module_name(e.name))
                          for e in lines.get("XLA Modules", []))
            mi = 0
            for e in sorted(lines.get("XLA Ops", []), key=lambda e: e.start_ns):
                while mi + 1 < len(mods) and mods[mi + 1][0] <= e.start_ns:
                    mi += 1
                mod = mods[mi][2] if mods and mods[mi][0] <= e.start_ns else ""
                name = e.name.split(" = ", 1)[0].strip().lstrip("%")
                ops.append(Op(dev, mod, name, e.start_ns, e.duration_ns))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append(Span(e.name, e.start_ns, e.duration_ns))
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s.start),
                 devices=devices)


def scope_map(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(module name, instruction name -> op_name path) of a compiled
    program's HLO text."""
    module = ""
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return module, out


def window_of(trace: Trace, name: str = "bench.window") -> tuple[float, float]:
    """[start, end] ns of the harness's window span."""
    for s in trace.spans:
        if s.name == name:
            return s.start, s.start + s.dur
    raise ValueError(f"no host span {name!r} in the trace")


def _clip(op: Op, w0: float, w1: float) -> float:
    return max(0.0, min(op.start + op.dur, w1) - max(op.start, w0))


def _merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _union_s(trace: Trace, keep, w0: float, w1: float) -> float:
    """Seconds, summed over chips, of the union of the intervals of the ops
    ``keep`` selects. A control-flow op (``while``, ``conditional``) spans
    the ops of its body, so a sum of durations would count them twice."""
    tot = 0.0
    for d in range(trace.devices):
        tot += sum(b - a for a, b in _merged(
            (max(o.start, w0), min(o.start + o.dur, w1)) for o in trace.ops
            if o.device == d and o.start < w1 and o.start + o.dur > w0
            and keep(o)))
    return tot / 1e9


def busy_intervals(trace: Trace, device: int, w0: float, w1: float):
    return _merged((max(o.start, w0), min(o.start + o.dur, w1))
                   for o in trace.ops
                   if o.device == device and o.start < w1
                   and o.start + o.dur > w0)


def busy_s(trace: Trace, w0: float, w1: float) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return _union_s(trace, lambda o: True, w0, w1) / trace.devices


def scope_s(trace: Trace, scopes: dict[str, dict[str, str]], scope: str,
            w0: float, w1: float) -> float:
    """Device seconds, summed over chips, in which an instruction whose
    op_name path has ``scope`` as a component ran."""
    def keep(o):
        return scope in scopes.get(o.module, {}).get(o.name, "").split("/")

    return _union_s(trace, keep, w0, w1)


def kernel_s(trace: Trace, kernel: str, w0: float, w1: float) -> float:
    """Device seconds, summed over chips, of a kernel's launches (the
    instruction is named after the kernel, ``<kernel>`` or
    ``<kernel>.<n>``)."""
    return _union_s(trace, lambda o: o.name == kernel
                    or o.name.startswith(kernel + "."), w0, w1)


def module_s(trace: Trace, module: str, w0: float, w1: float) -> float:
    """Device seconds, summed over chips, of one program's operations."""
    return _union_s(trace, lambda o: o.module == module, w0, w1)


def self_times(trace: Trace, w0: float, w1: float) -> list[float]:
    """Each op's own nanoseconds in the window: its span less the spans of
    the ops nested directly inside it (a loop's body, a branch)."""
    own = [_clip(o, w0, w1) for o in trace.ops]
    for d in range(trace.devices):
        idx = sorted((i for i, o in enumerate(trace.ops) if o.device == d),
                     key=lambda i: (trace.ops[i].start, -trace.ops[i].dur))
        stack: list[int] = []
        for i in idx:
            o = trace.ops[i]
            while stack and (trace.ops[stack[-1]].start
                             + trace.ops[stack[-1]].dur <= o.start):
                stack.pop()
            if stack:
                own[stack[-1]] -= _clip(o, w0, w1)
            stack.append(i)
    return [max(t, 0.0) for t in own]


def _base(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def _scope_label(path: str) -> str:
    for part in path.split("/"):
        if "." in part and not part.startswith("jit("):
            return part
    return ""


def top_ops(trace: Trace, scopes, w0: float, w1: float, k: int = 10):
    """The ``k`` device operations that took most time of their own (less
    the ops nested in them), per chip on average:
    ``[[module:scope:instruction, seconds], ...]``."""
    acc: dict[str, float] = defaultdict(float)
    for o, t in zip(trace.ops, self_times(trace, w0, w1)):
        if t <= 0:
            continue
        scope = _scope_label(scopes.get(o.module, {}).get(o.name, ""))
        acc[f"{o.module}:{scope}:{_base(o.name)}"] += t
    n = max(trace.devices, 1)
    return [[name, t / n / 1e9]
            for name, t in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, w0: float, w1: float, k: int = 10):
    """Idle time of chip 0 in the window, by what the host was doing: each
    idle nanosecond goes to the innermost harness span open at it.
    ``[[span name, seconds], ...]``, most first."""
    busy = busy_intervals(trace, 0, w0, w1)
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    spans = [s for s in trace.spans if s.name != "bench.window"]
    starts = [s.start for s in spans]
    longest = max((s.dur for s in spans), default=0.0)
    acc: dict[str, float] = defaultdict(float)
    for a, b in gaps:
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        cand = [s for s in spans[lo:hi] if s.start < b and s.start + s.dur > a]
        cuts = sorted({a, b} | {x for s in cand for x in (s.start,
                                                         s.start + s.dur)
                                if a < x < b})
        for x0, x1 in zip(cuts, cuts[1:]):
            mid = 0.5 * (x0 + x1)
            open_ = [s for s in cand if s.start <= mid < s.start + s.dur]
            name = max(open_, key=lambda s: s.start).name if open_ \
                else "(no span)"
            acc[name] += x1 - x0
    return [[name, t / 1e9]
            for name, t in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
