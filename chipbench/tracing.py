"""Reduction of a profiler trace to the benchmark's per-layer numbers.

A trace holds, on the device plane, one event per XLA operation ("XLA Ops")
and per program execution ("XLA Modules"), and on the host plane the spans
of the threads that serve and consume: the benchmark's own (``READ_SPAN``
around the store view's read, ``STAGE_SPAN`` around the engine's read plus
page build, ``WINDOW_SPAN`` around the traced session) beside JAX's
(dispatch, host-to-device transfer).  All share one clock.
"""

from __future__ import annotations

import dataclasses
import re

READ_SPAN = "chipbench.read"
STAGE_SPAN = "chipbench.stage"
WINDOW_SPAN = "chipbench.window"
PAGE_BUILD = "page_build"  # a stage span less the reads inside it
OWN_SPANS = (READ_SPAN, STAGE_SPAN, WINDOW_SPAN)

_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """HLO instruction name of an "XLA Ops" event ("%copy.13 = u32[..] ..."
    -> "copy.13")."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """An instruction name without XLA's uniquing suffix ("copy.13" -> "copy")."""
    return _SUFFIX.sub("", name)


@dataclasses.dataclass
class Trace:
    ops: list  # (instruction name, start_ns, end_ns), device "XLA Ops"
    modules: list  # (program name, start_ns, end_ns), device "XLA Modules"
    host: list  # (line index, span name, start_ns, end_ns), host threads
    n_devices: int

    # -- window ----------------------------------------------------------------
    def window(self):
        """(start_ns, end_ns) of the traced session, or None."""
        w = [(s, e) for _, n, s, e in self.host if n == WINDOW_SPAN]
        return (min(s for s, _ in w), max(e for _, e in w)) if w else None

    def _in_window(self, events):
        w = self.window()
        if w is None:
            return []
        lo, hi = w
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]

    # -- device ----------------------------------------------------------------
    def busy_intervals(self) -> list:
        """Merged intervals in which some operation ran on a device."""
        return merge((s, e) for _, s, e in self._in_window(self.ops))

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the devices."""
        return sum(e - s for s, e in self.busy_intervals()) / 1e9 / max(self.n_devices, 1)

    def window_s(self) -> float:
        w = self.window()
        return (w[1] - w[0]) / 1e9 if w else 0.0

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the operations named `kernel` (any suffix)."""
        return sum(
            e - s for n, s, e in self._in_window(self.ops) if base_name(n) == kernel
        ) / 1e9

    def program_s(self) -> float:
        """Device seconds of every program execution in the window."""
        return sum(e - s for _, s, e in self._in_window(self.modules)) / 1e9

    # -- host ------------------------------------------------------------------
    def spans(self, name: str) -> list:
        """(line, start_ns, end_ns) of the host spans called `name`."""
        return [(ln, s, e) for ln, n, s, e in self.host if n == name]

    def page_builds(self) -> list:
        """(line, start_ns, end_ns, self_ns) of each stage span: its own
        time less the read spans nested in it on the same thread."""
        reads = self.spans(READ_SPAN)
        out = []
        for ln, s, e in self.spans(STAGE_SPAN):
            inner = sum(
                min(re_, e) - max(rs, s) for rl, rs, re_ in reads
                if rl == ln and re_ > s and rs < e
            )
            out.append((ln, s, e, (e - s) - inner))
        return out

    # -- breakdown ---------------------------------------------------------------
    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time in the window, and the
        longest device-idle gaps, each labelled by the host span that
        overlaps it most (a stage span counts as page build where no read
        is nested in it)."""
        totals: dict = {}
        for n, s, e in self._in_window(self.ops):
            totals[n] = totals.get(n, 0.0) + (e - s) / 1e9
        device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        w = self.window()
        gaps = []
        if w is not None:
            t = w[0]
            for s, e in self.busy_intervals() + [(w[1], w[1])]:
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        labelled = self._host_spans_for_labels()
        idle = [[_label(g, labelled), (g[1] - g[0]) / 1e9] for g in gaps]
        return {"device_ops": [[n, v] for n, v in device_ops], "idle_gaps": idle}

    def _host_spans_for_labels(self) -> list:
        out = []
        reads = self.spans(READ_SPAN)
        for ln, n, s, e in self.host:
            if n in (WINDOW_SPAN, STAGE_SPAN):
                continue
            out.append((n, s, e))
        for ln, s, e in self.spans(STAGE_SPAN):
            # the stage span's time outside its reads is the page build
            t = s
            for rl, rs, re_ in sorted(r for r in reads if r[0] == ln):
                if re_ <= s or rs >= e:
                    continue
                if rs > t:
                    out.append((PAGE_BUILD, t, rs))
                t = max(t, re_)
            if e > t:
                out.append((PAGE_BUILD, t, e))
        return out


def _label(gap, spans) -> str:
    lo, hi = gap
    overlap: dict = {}
    for n, s, e in spans:
        if e > lo and s < hi:
            overlap[n] = overlap.get(n, 0.0) + min(e, hi) - max(s, lo)
    if not overlap:
        return "no_host_span"
    return max(overlap.items(), key=lambda kv: kv[1])[0]


def merge(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host, n_dev = [], [], [], 0
    line_no = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            before = len(ops)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
                elif line.name == "XLA Modules":
                    modules += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                                for ev in line.events]
            n_dev += len(ops) > before  # a chip the traced work ran on
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                line_no += 1
                events = [(line_no, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events]
                # the benchmark's threads: those that opened one of its spans
                # (JAX's runtime threads never do)
                if any(e[1] in OWN_SPANS for e in events):
                    host += events
    return Trace(ops=ops, modules=modules, host=host, n_devices=n_dev)
