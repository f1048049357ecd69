"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Modules`` line
holds one event per program execution (named ``jit_<function>(<id>)``)
and the ``XLA Ops`` line one event per HLO operation. The host plane
holds the benchmark's own spans, ``jax.profiler.TraceAnnotation`` events
named ``bench.<call>``; ``bench.window`` spans the measured window.
Host and device events share one clock in the trace.

* busy: the union of the op intervals inside the window, per chip, then
  averaged over the chips; idle = window - busy;
* device time of a program: the summed duration of its module events,
  per chip;
* device time of a kernel: the summed duration of the op events whose
  name (the op's HLO text, ``%compact_rows_pallas.6 = (s32[1024,256]...``)
  matches it, per chip;
* idle gaps: the gaps between busy intervals, each labelled with the
  innermost benchmark span the host was in at the gap's middle.
"""
from __future__ import annotations

import collections
import gzip
import pathlib
import re

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")
_CONTAINER = re.compile(r"^%(while|cond|conditional|call)[.\d]* = ")


def _short(hlo: str) -> str:
    """An op event's name is its HLO text: keep the name and the result
    type, ``%fusion.7 = s32[16777216]``."""
    name, _, rest = hlo.partition(" = ")
    return f"{name} = {rest.split('{')[0].split(' ')[0]}" if rest else name


def load(path) -> "TraceSummary":
    """Summarise the trace at ``path``: an ``.xplane.pb`` file, a gzipped
    one, or a directory holding one."""
    from jax.profiler import ProfileData
    p = pathlib.Path(path)
    if p.is_dir():
        found = sorted(p.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = found[-1]
    if p.suffix == ".gz":
        return TraceSummary(ProfileData.from_serialized_xspace(
            gzip.decompress(p.read_bytes())))
    return TraceSummary(ProfileData.from_file(str(p)))


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of (start, end) intervals as sorted, disjoint intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


class TraceSummary:
    def __init__(self, pdata):
        self.spans = []            # (start_ns, end_ns, name) host spans
        self.devices = []          # per chip: dict(ops=[...], modules=[...])
        for plane in pdata.planes:
            if _DEVICE.match(plane.name):
                ops, mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for e in line.events:
                            ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                        e.name))
                    elif line.name == "XLA Modules":
                        for e in line.events:
                            mods.append((e.start_ns, e.start_ns + e.duration_ns,
                                         _SUFFIX.sub("", e.name)))
                self.devices.append(dict(ops=ops, modules=mods))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.spans.append((e.start_ns,
                                               e.start_ns + e.duration_ns,
                                               e.name))
        win = [s for s in self.spans if s[2] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        self.t0, self.t1 = win[0][0], win[0][1]
        for d in self.devices:
            d["ops"] = [o for o in d["ops"]
                        if o[1] > self.t0 and o[0] < self.t1]
            d["modules"] = [m for m in d["modules"]
                            if m[1] > self.t0 and m[0] < self.t1]
            iv = np.asarray([(max(o[0], self.t0), min(o[1], self.t1))
                             for o in d["ops"]], np.float64).reshape(-1, 2)
            d["busy"] = _merge(iv)

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return float(np.mean([np.sum(d["busy"][:, 1] - d["busy"][:, 0])
                              for d in self.devices])) * 1e-9

    def _per_chip(self, total: float) -> float:
        return total / max(1, self.n_devices)

    def module_seconds(self, name: str) -> float:
        """Device seconds of the program ``name`` (e.g.
        ``jit_step_update_edges``), per chip."""
        return self._per_chip(sum(m[1] - m[0] for d in self.devices
                                  for m in d["modules"] if m[2] == name)
                              * 1e-9)

    def module_count(self, name: str) -> float:
        """Executions of the program ``name``, per chip."""
        return self._per_chip(sum(1 for d in self.devices
                                  for m in d["modules"] if m[2] == name))

    def op_seconds(self, pattern: str, module: str | None = None) -> float:
        """Device seconds, per chip, of the ops whose event name matches
        the regular expression ``pattern``, optionally only inside
        executions of the program ``module``."""
        rx = re.compile(pattern)
        total = 0.0
        for d in self.devices:
            inside = self._inside(d, module)
            for o in d["ops"]:
                if rx.search(o[2]) and inside(o):
                    total += o[1] - o[0]
        return self._per_chip(total * 1e-9)

    @staticmethod
    def _inside(d, module):
        if module is None:
            return lambda o: True
        iv = np.asarray([(m[0], m[1]) for m in d["modules"] if m[2] == module],
                        np.float64).reshape(-1, 2)
        iv = iv[np.argsort(iv[:, 0])] if len(iv) else iv
        starts = iv[:, 0]

        def inside(o):
            k = np.searchsorted(starts, o[0], side="right") - 1
            return k >= 0 and o[0] >= iv[k, 0] and o[1] <= iv[k, 1] + 1
        return inside

    def top_ops(self, n: int = 10):
        """The ``n`` device operations that took most time, as
        [``module/op = result type``, seconds per chip]. Loops and
        conditionals are left out: their bodies' ops are counted."""
        acc = collections.Counter()
        for d in self.devices:
            mods = sorted(d["modules"])
            starts = [m[0] for m in mods]
            for o in d["ops"]:
                if _CONTAINER.match(o[2]):
                    continue
                k = np.searchsorted(starts, o[0], side="right") - 1
                mod = mods[k][2] if k >= 0 and o[0] <= mods[k][1] else "?"
                acc[f"{mod}/{_short(o[2])}"] += (o[1] - o[0]) * 1e-9
        return [[k, self._per_chip(v)] for k, v in acc.most_common(n)]

    def idle_gaps(self, n: int = 10):
        """Idle seconds per chip between busy intervals inside the window,
        summed by the host span they fell in, the ``n`` largest as
        [``<span> x<gaps>``, seconds]."""
        # paint the timeline's segments with span names, longest span
        # first, so the innermost span a point lies in names it
        spans = [s for s in self.spans if s[2] != WINDOW_SPAN]
        names = ["outside any call"] + sorted({s[2] for s in spans})
        bounds = np.unique(np.asarray([t for s in spans for t in s[:2]],
                                      np.float64))
        label = np.zeros(max(len(bounds), 1), np.int64)
        for s, e, name in sorted(spans, key=lambda s: s[0] - s[1]):
            lo, hi = np.searchsorted(bounds, [s, e])
            label[lo:hi] = names.index(name)
        acc, cnt = collections.Counter(), collections.Counter()
        for d in self.devices:
            edges = np.concatenate([[self.t0], d["busy"].ravel(), [self.t1]])
            gs, ge = edges[0::2], edges[1::2]
            keep = ge > gs
            gs, ge = gs[keep], ge[keep]
            seg = np.searchsorted(bounds, 0.5 * (gs + ge), side="right") - 1
            inside = (seg >= 0) & (seg < len(bounds) - 1)
            lab = np.where(inside, label[np.clip(seg, 0, len(label) - 1)], 0)
            for k in np.unique(lab):
                sel = lab == k
                acc[names[k]] += float(np.sum(ge[sel] - gs[sel])) * 1e-9
                cnt[names[k]] += int(np.sum(sel))
        return [[f"{k} x{cnt[k]}", self._per_chip(v)]
                for k, v in acc.most_common(n)]
