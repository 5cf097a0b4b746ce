"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation run on it; the host's spans (the harness's
``jax.profiler.TraceAnnotation``s: ``window``, and the runners' own such
as ``run_schedule`` and ``eval``) are events of the host plane's
threads. All are on one clock, in nanoseconds.

Only the ``window`` span's interval counts. A chip is busy where any of
its operations runs (the union of their intervals); its idle share is one
less busy over the window. An operation's time is its self time: its
duration less what the events nested inside it on the same line cover.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Tuple

Interval = Tuple[str, float, float]     # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
# an op event's name is its HLO text: "%name = type opcode(operands), ..."
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<type>[^{\s]*).*?\s"
                  r"(?P<op>[a-z][a-z0-9-]*)\(")


def op_label(text: str) -> str:
    """A short label of an op event: ``name opcode type``, e.g.
    ``fusion.118 fusion bf16[320,28,28]``; other names as they are."""
    m = _HLO.match(text)
    if not m:
        return text[:120]
    return f"{m['name']} {m['op']} {m['type']}".strip()


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(ops: List[Interval]) -> Dict[str, float]:
    """Self time (ns) by operation name; nested events are subtracted from
    the event they sit in."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [name, start, end, child_ns]

    def close(frame):
        name, s, e, child = frame
        out[name] = out.get(name, 0.0) + (e - s) - child

    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]      # chip -> its operations
    spans: List[Interval]               # host spans
    window: Tuple[float, float]         # the "window" span

    @classmethod
    def load(cls, trace_dir: str, chips: int, span_names) -> "Trace":
        import jax
        paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        data = jax.profiler.ProfileData.from_file(paths[0])
        ops: Dict[int, List[Interval]] = {}
        spans: List[Interval] = []
        labels: Dict[str, str] = {}     # an op's text repeats every step
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    out = ops.setdefault(int(m.group(1)), [])
                    for e in line.events:
                        name = e.name
                        if name not in labels:
                            labels[name] = op_label(name)
                        out.append((labels[name], e.start_ns,
                                    e.start_ns + e.duration_ns))
                elif plane.name.startswith("/host:"):
                    spans.extend((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events if e.name in span_names)
        windows = [s for s in spans if s[0] == "window"]
        if len(windows) != 1:
            raise RuntimeError(f"expected one window span, found {windows}")
        ops = {d: v for d, v in sorted(ops.items())[:chips]}
        if not any(ops.values()):
            raise RuntimeError("no operation ran on the device in the window")
        return cls(ops=ops, spans=spans, window=windows[0][1:])

    # -- what the metrics read ------------------------------------------
    def _clip(self, dev: int) -> List[Interval]:
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in self.ops[dev]
                if e > lo and s < hi]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, dev: int) -> List[Tuple[float, float]]:
        return _union([(s, e) for _, s, e in self._clip(dev)])

    def device_busy_s(self) -> Dict[int, float]:
        return {d: sum(e - s for s, e in self.busy_intervals(d)) * 1e-9
                for d in self.ops}

    @property
    def busy_s(self) -> float:
        """Busy seconds, the mean over the chips."""
        b = self.device_busy_s()
        return sum(b.values()) / len(b)

    def op_seconds(self) -> Dict[int, Dict[str, float]]:
        """Per chip, each operation name's self seconds in the window."""
        return {d: {n: ns * 1e-9 for n, ns in _self_times(self._clip(d)).items()}
                for d in self.ops}

    def collective_s(self) -> float:
        """Seconds in collective operations (by name or opcode), the mean
        over the chips."""
        per = [sum(v for n, v in ops.items()
                   if COLLECTIVE.search(" ".join(n.split()[:2])))
               for ops in self.op_seconds().values()]
        return sum(per) / len(per)

    def idle_gaps(self, dev: int) -> List[Tuple[float, float]]:
        lo, hi = self.window
        out, t = [], lo
        for s, e in self.busy_intervals(dev):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def _host_doing(self, s: float, e: float) -> str:
        """The host span (other than the window) that overlaps [s, e] the
        most; ``host`` where none does."""
        best, name = 0.0, "host"
        for n, a, b in self.spans:
            if n == "window":
                continue
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, n
        return name

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most time (mean seconds over chips) and
        the longest idle gaps of the first chip, named by what the host was
        doing in each."""
        per = self.op_seconds()
        total: Dict[str, float] = {}
        for ops in per.values():
            for n, v in ops.items():
                total[n] = total.get(n, 0.0) + v / len(per)
        ops = sorted(total.items(), key=lambda x: -x[1])[:top]
        first = min(self.ops)
        gaps = sorted(self.idle_gaps(first), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[self._host_doing(s, e), (e - s) * 1e-9]
                              for s, e in gaps]}
