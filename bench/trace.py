"""Reduce a profiler trace of the window to device times by layer.

A trace is read into plain records: device-op intervals per chip (the
profiler's "XLA Ops" line of each device plane, with the module that
ran them from the "XLA Modules" line) and host spans (the engine's
`obs.trace` spans, recorded with `xla=True`, and the benchmark's own
`bench.window` span, which bounds the window on the same clock).
Each op is attributed through the compiled chunk program's HLO text:
its opcode, the `round.*` named scope in its metadata, and whether it
is a Pallas kernel (`tpu_custom_call`).

Busy time is the union of op intervals on a chip; an op's own time is
its interval less the ops nested in it (a `while` holds its body).
Async copies on their own line are not counted as busy.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
ENGINE_SPANS = ("chunk", "compile", "dispatch", "history_drain", "eval",
                "transfer", "checkpoint", "health")
CHUNK_MODULE = "jit_chunk"
_SCOPE = re.compile(r"round\.[A-Za-z_]+")


# opcodes that only move or re-lay data: XLA stages a kernel's operand
# into on-chip memory through them, so the kernel's own time leaves out
# the reads they make
STAGING = ("bitcast", "copy", "copy-start", "copy-done", "pad", "reshape",
           "slice", "transpose")


@dataclasses.dataclass(frozen=True)
class OpInfo:
    opcode: str
    scope: str            # "round.probe", ..., or "" when unscoped
    kernel: bool          # a Pallas kernel (tpu_custom_call)
    operands: Tuple[str, ...] = dataclasses.field(default=(), compare=False)


@dataclasses.dataclass
class Op:
    chip: int
    module: str           # "jit_chunk", "jit_evaluate", ...
    name: str             # HLO instruction name
    start: float          # ns
    dur: float            # ns
    self_ns: float = 0.0
    info: Optional[OpInfo] = None


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    window: Tuple[float, float]
    chips: List[int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


# ------------------------------------------------------------ HLO text

def _split_type(rest: str) -> Tuple[str, str]:
    """('type', 'opcode(...)...') from the text after ' = '."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].lstrip()
    head, _, tail = rest.partition(" ")
    return head, tail


def parse_hlo(text: str) -> Dict[str, OpInfo]:
    """{instruction name: OpInfo} for every instruction of an HLO
    module's text."""
    out = {}
    for line in text.splitlines():
        s = line.strip()
        if s.startswith("ROOT "):
            s = s[5:]
        if not s.startswith("%") or " = " not in s:
            continue
        name, rest = s[1:].split(" = ", 1)
        _, body = _split_type(rest)
        opcode, _, args = body.partition("(")
        opcode = opcode.strip()
        m = re.search(r'op_name="([^"]*)"', body)
        scope = _SCOPE.search(m.group(1)) if m else None
        out[name] = OpInfo(
            opcode=opcode, scope=scope.group(0) if scope else "",
            kernel=(opcode == "custom-call"
                    and 'custom_call_target="tpu_custom_call"' in body),
            operands=tuple(re.findall(r"%([\w.\-]+)",
                                      _split_type("(" + args)[0])))
    return out


# ------------------------------------------------------------ reduction

def op_name(event_name: str) -> str:
    """HLO instruction name of a device-op event ('%fusion.4 = ...')."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def attach(ops: List[Op], hlo: Dict[str, OpInfo]) -> None:
    """Set each chunk op's OpInfo, and each op's own (self) time."""
    for op in ops:
        if op.module.startswith(CHUNK_MODULE):
            op.info = hlo.get(op.name)
    by_chip: Dict[int, List[Op]] = {}
    for op in ops:
        by_chip.setdefault(op.chip, []).append(op)
    for chip_ops in by_chip.values():
        chip_ops.sort(key=lambda o: (o.start, -o.dur))
        stack: List[Op] = []
        for op in chip_ops:
            op.self_ns = op.dur
            while stack and stack[-1].start + stack[-1].dur <= op.start:
                stack.pop()
            if stack:
                stack[-1].self_ns -= min(op.dur, stack[-1].start
                                         + stack[-1].dur - op.start)
            stack.append(op)


def clip(trace: Trace) -> List[Op]:
    """Ops that start inside the window."""
    a, b = trace.window
    return [o for o in trace.ops if a <= o.start < b]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_intervals(trace: Trace, chip: int) -> List[Tuple[float, float]]:
    a, b = trace.window
    return union((max(o.start, a), min(o.start + o.dur, b))
                 for o in trace.ops
                 if o.chip == chip and o.start < b and o.start + o.dur > a)


def busy_s(trace: Trace) -> float:
    """Seconds with an op running, averaged over the chips."""
    tot = [sum(y - x for x, y in busy_intervals(trace, c))
           for c in trace.chips]
    return sum(tot) / len(tot) * 1e-9


def scope_s(trace: Trace, scope: str) -> float:
    """Own device time of the chunk ops under `scope`, averaged over the
    chips."""
    tot = sum(o.self_ns for o in clip(trace)
              if o.info is not None and o.info.scope == scope)
    return tot / len(trace.chips) * 1e-9


def kernel_s(trace: Trace, scope: str) -> Optional[float]:
    """Device time of the Pallas kernels under `scope` per chip, or None
    when none ran there."""
    ks = [o for o in clip(trace)
          if o.info is not None and o.info.kernel and o.info.scope == scope]
    if not ks:
        return None
    return sum(o.self_ns for o in ks) / len(trace.chips) * 1e-9


def staged_kernel_s(trace: Trace, scope: str,
                    hlo: Dict[str, OpInfo]) -> Optional[float]:
    """Device time of the Pallas kernels under `scope` and of the ops
    that stage their operands (chains of STAGING opcodes back from each
    kernel's operands, whatever their scope), per chip; None when no
    such kernel ran."""
    ops = clip(trace)
    names = {o.name for o in ops
             if o.info is not None and o.info.kernel and o.info.scope == scope}
    if not names:
        return None
    todo = [a for n in names for a in hlo[n].operands]
    while todo:
        n = todo.pop()
        if n not in names and n in hlo and hlo[n].opcode in STAGING:
            names.add(n)
            todo.extend(hlo[n].operands)
    tot = sum(o.self_ns for o in ops if o.info is not None
              and o.name in names)
    return tot / len(trace.chips) * 1e-9


def label(op: Op) -> str:
    if op.info is None:
        return f"{op.module}/{op.name}"
    return f"{op.info.scope or 'chunk'}/{op.name}"


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """[[scoped name, seconds], ...] by own time, summed over chips."""
    tot: Dict[str, float] = {}
    for o in clip(trace):
        tot[label(o)] = tot.get(label(o), 0.0) + o.self_ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """[[host span open during the gap, seconds], ...]: the longest gaps
    between busy intervals on the first chip, each named by the
    innermost engine span that covers the gap's middle."""
    a, b = trace.window
    busy = busy_intervals(trace, trace.chips[0])
    gaps, t = [], a
    for x, y in busy:
        if x > t:
            gaps.append((t, x))
        t = max(t, y)
    if b > t:
        gaps.append((t, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for x, y in gaps[:n]:
        mid = (x + y) / 2
        cover = [s for s in trace.spans
                 if s.name != WINDOW_SPAN and s.start <= mid < s.start
                 + s.dur]
        name = min(cover, key=lambda s: s.dur).name if cover else "host"
        out.append([name, (y - x) * 1e-9])
    return out


# ------------------------------------------------------------ loading

def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no profile under {directory}")
    return found[-1]


def load(path: str, hlo: Dict[str, OpInfo]) -> Trace:
    """Read an .xplane.pb written by `jax.profiler` into a Trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    chips: List[int] = []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" in lines:
            chip = len(chips)
            chips.append(chip)
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(")[0])
                          for e in lines.get("XLA Modules", ()).events)
            mi = 0
            for e in sorted(lines["XLA Ops"].events,
                            key=lambda e: e.start_ns):
                while mi + 1 < len(mods) and mods[mi][1] < e.start_ns:
                    mi += 1
                mod = (mods[mi][2] if mods and mods[mi][0] <= e.start_ns
                       <= mods[mi][1] else "")
                ops.append(Op(chip, mod, op_name(e.name), e.start_ns,
                              e.duration_ns))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == WINDOW_SPAN or e.name in ENGINE_SPANS:
                        spans.append(Span(e.name, e.start_ns,
                                          e.duration_ns))
    if not chips:
        raise ValueError(f"{path}: no device plane with an 'XLA Ops' line")
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w = win[0]
    trace = Trace(ops, spans, (w.start, w.start + w.dur), chips)
    attach(trace.ops, hlo)
    return trace
