"""Reduce a profiler trace (``.xplane.pb``) to what the metric readers use.

Read with ``jax.profiler.ProfileData`` alone.  Device operations are the
events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; the
benchmark's own spans are the host events with the names the system module
records (``jax.profiler.TraceAnnotation``), on the same clock.  The traced
window runs from the first span's start to the last span's end.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
OUTSIDE = "outside spans"
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def op_label(hlo_text: str) -> str:
    """``"<instruction> <opcode>"`` from an op event's HLO text, e.g.
    ``"%pad.0 = s32[40,1664]{...} pad(...)"`` -> ``"pad.0 pad"``."""
    name, _, rest = hlo_text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return f"{name.lstrip('%')} {m.group(1)}" if m else name.lstrip("%")


def _union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint sorted intervals covering ``[starts[i], ends[i])``."""
    if not len(starts):
        return starts, ends
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], np.maximum.accumulate(ends[o])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(e[idx[1:] - 1], e[-1])


@dataclasses.dataclass
class TraceSummary:
    """Device operations and host spans of one traced window (ns)."""

    op_names: list       # per device op
    op_start: np.ndarray
    op_end: np.ndarray
    op_device: np.ndarray
    n_devices: int
    spans: dict          # span name -> (starts, ends)
    window: tuple        # (start, end)

    @property
    def window_ns(self) -> float:
        return float(self.window[1] - self.window[0])

    def _busy(self, device: int):
        m = self.op_device == device
        s, e = _union(self.op_start[m], self.op_end[m])
        lo, hi = self.window
        return np.clip(s, lo, hi), np.clip(e, lo, hi)

    @property
    def busy_ns(self) -> float:
        """Union of device op intervals in the window, averaged over chips."""
        total = 0.0
        for d in range(self.n_devices):
            s, e = self._busy(d)
            total += float(np.sum(e - s))
        return total / max(self.n_devices, 1)

    def busy_between(self, starts, ends, device: int = 0) -> np.ndarray:
        """Device-busy ns inside each ``[starts[i], ends[i])``."""
        starts, ends = np.asarray(starts, float), np.asarray(ends, float)
        s, e = self._busy(device)
        if not len(s):
            return np.zeros(len(starts))
        before = np.concatenate([[0.0], np.cumsum(e - s)])  # busy before s[j]

        def covered(t):  # busy ns before time t
            j = np.searchsorted(s, t, side="right") - 1   # last start <= t
            jj = np.maximum(j, 0)
            inside = np.clip(np.minimum(t, e[jj]) - s[jj], 0.0, None)
            return np.where(j >= 0, before[jj] + inside, 0.0)

        return covered(ends) - covered(starts)

    def op_ns(self, match) -> tuple[float, int]:
        """Summed device ns and count of the ops whose name ``match`` takes."""
        m = np.array([bool(match(n)) for n in self.op_names], bool)
        return float(np.sum(self.op_end[m] - self.op_start[m])), int(m.sum())

    def idle_gaps(self, device: int = 0):
        """``(start, end, label)`` of every idle gap in the window, labelled
        by the innermost span open at its midpoint."""
        s, e = self._busy(device)
        lo, hi = self.window
        g_s = np.concatenate([[lo], e])
        g_e = np.concatenate([s, [hi]])
        keep = g_e > g_s
        g_s, g_e = g_s[keep], g_e[keep]
        mid = 0.5 * (g_s + g_e)
        label = np.full(len(mid), OUTSIDE, object)
        depth = np.full(len(mid), -np.inf)
        for name, (ss, se) in self.spans.items():
            o = np.argsort(ss)
            ss, se = ss[o], se[o]
            i = np.searchsorted(ss, mid, side="right") - 1
            inside = (i >= 0) & (mid < se[np.maximum(i, 0)])
            start = np.where(inside, ss[np.maximum(i, 0)], -np.inf)
            newer = inside & (start > depth)  # the later-opened span is inner
            label[newer] = name
            depth[newer] = start[newer]
        return g_s, g_e, label

    def breakdown(self, top: int = 10) -> dict:
        """Device ops with most summed time, and idle time by the span the
        host was in, in seconds."""
        per_op = {}
        for n, d in zip(self.op_names, (self.op_end - self.op_start).tolist()):
            per_op[n] = per_op.get(n, 0.0) + d
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        g_s, g_e, label = self.idle_gaps()
        per_gap = {}
        for lab, d in zip(label.tolist(), (g_e - g_s).tolist()):
            per_gap[lab] = per_gap.get(lab, 0.0) + d
        gaps = sorted(per_gap.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v / 1e9] for n, v in ops],
                "idle_gaps": [[n, v / 1e9] for n, v in gaps]}


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def reduce_trace(path: Path, span_names) -> TraceSummary:
    """Summarise the trace at ``path`` (a file, or a directory holding one)."""
    from jax.profiler import ProfileData

    path = Path(path)
    data = ProfileData.from_file(str(path if path.is_file() else find_xplane(path)))
    names, starts, ends, devs = [], [], [], []
    spans = {n: ([], []) for n in span_names}
    n_dev = 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            d = n_dev
            n_dev += 1
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    names.append(op_label(ev.name))
                    starts.append(ev.start_ns)
                    ends.append(ev.start_ns + ev.duration_ns)
                    devs.append(d)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name][0].append(ev.start_ns)
                        spans[ev.name][1].append(ev.start_ns + ev.duration_ns)
    spans = {n: (np.array(s, float), np.array(e, float)) for n, (s, e) in spans.items()}
    outer = spans[span_names[0]]
    if not len(outer[0]):
        raise ValueError(f"no {span_names[0]!r} span in the trace")
    window = (float(outer[0].min()), float(outer[1].max()))
    return TraceSummary(names, np.array(starts, float), np.array(ends, float),
                        np.array(devs, int), n_dev, spans, window)
