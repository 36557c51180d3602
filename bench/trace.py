"""Profiler capture of the measured window and its reduction to events.

The harness wraps the window and each call it makes in
``jax.profiler.TraceAnnotation`` spans (``SPANS``).  A traced run records
the window with the host tracer at level 1 (the harness's spans and the
runtime's own annotations, no Python function tracing) and reads back:

* for every device plane (``/device:TPU:<n>``), its ``XLA Modules`` line
  (one event per program execution, named ``jit_<function>(<id>)``) and its
  ``XLA Ops`` line (one event per operation executed, the basis of busy
  time);
* on the host, the thread that holds the harness's ``window`` span, with all
  its events, to say what the host was doing in each idle gap.

Host and device events share one clock in the profiler's trace.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

SPANS = ("setup", "window", "post", "tick", "complete", "check")
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def program_name(module: str) -> str:
    """A module event's name without the compilation id it carries."""
    return _MODULE_ID.sub("", module)


def start(log_dir: Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def _merge(intervals: List[Tuple[float, float]], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi], in order."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Trace:
    """The events of one traced window."""

    def __init__(self, window: Event, modules: List[List[Event]],
                 busy: List[List[Tuple[float, float]]], host: List[Event]):
        self.window = window
        self.modules = modules     # per device, program executions
        self.busy = busy           # per device, merged busy intervals
        self.host = host           # harness thread's events in the window

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        host_line = None
        window = None
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window":
                        host_line = line
                        window = Event("window", ev.start_ns, ev.end_ns)
                        break
                if window is not None:
                    break
            if window is not None:
                break
        if window is None:
            raise ValueError("the trace holds no harness 'window' span")
        lo, hi = window.start_ns, window.end_ns
        host = [Event(ev.name, ev.start_ns, ev.end_ns)
                for ev in host_line.events
                if ev.end_ns > lo and ev.start_ns < hi]
        modules: List[List[Event]] = []
        busy: List[List[Tuple[float, float]]] = []
        for plane in pd.planes:
            if not re.match(r"^/device:[A-Z]+:\d+$", plane.name):
                continue
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            modules.append([
                Event(ev.name, ev.start_ns, ev.end_ns)
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ())
                if lo <= ev.start_ns < hi])
            busy.append(_merge([(ev.start_ns, ev.end_ns)
                                for ev in lines["XLA Ops"].events],
                               lo, hi))
        return cls(window, modules, busy, host)

    @classmethod
    def from_dir(cls, log_dir: Path) -> "Trace":
        import jax
        files = sorted(Path(log_dir).glob("**/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no profiler trace under {log_dir}")
        return cls.from_profile(jax.profiler.ProfileData.from_file(
            str(files[-1])))

    @classmethod
    def from_bytes(cls, data: bytes) -> "Trace":
        import jax
        return cls.from_profile(
            jax.profiler.ProfileData.from_serialized_xspace(data))

    # ------------------------------------------------------------ readings
    @property
    def window_s(self) -> float:
        return self.window.seconds

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in b) for b in self.busy) \
            / len(self.busy) / 1e9

    def module_seconds(self) -> Dict[str, float]:
        """Device seconds per program, summed over devices."""
        out: Dict[str, float] = {}
        for dev in self.modules:
            for ev in dev:
                name = program_name(ev.name)
                out[name] = out.get(name, 0.0) + ev.seconds
        return out

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Stretches of the window in which the first device ran nothing."""
        if not self.busy:
            return [(self.window.start_ns, self.window.end_ns)]
        gaps, t = [], self.window.start_ns
        for s, e in self.busy[0]:
            if s > t:
                gaps.append((t, s))
            t = e
        if self.window.end_ns > t:
            gaps.append((t, self.window.end_ns))
        return gaps

    def host_label(self, t: float) -> str:
        """What the host was doing at ``t``: the innermost harness span,
        and the innermost runtime annotation inside it, if any."""
        around = [ev for ev in self.host if ev.start_ns <= t < ev.end_ns]
        spans = [ev for ev in around if ev.name in SPANS]
        other = [ev for ev in around if ev.name not in SPANS]
        label = min(spans, key=lambda ev: ev.end_ns - ev.start_ns).name \
            if spans else "host"
        if other:
            inner = min(other, key=lambda ev: ev.end_ns - ev.start_ns)
            label += "/" + inner.name
        return label

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.module_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:n]
        return dict(
            device_ops=[[name, s] for name, s in ops[:n]],
            idle_gaps=[[self.host_label((s + e) / 2), (e - s) / 1e9]
                       for s, e in gaps])
