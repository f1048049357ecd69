"""The store's own trace events, read beside ``tracereduce``'s.

``tracereduce.TraceSummary`` keeps each device op's event name (its HLO
text) and the benchmark's ``bench.*`` host spans. The store records two
kinds of events of its own:

* named scopes (``jax.named_scope``) in its jitted programs. The trace
  carries each op's scope path in the ``tf_op`` stat of the op's event
  metadata, JAX's name stack: ``jit(step_update_edges)/probe/gather:``.
  ``jax.profiler.ProfileData`` does not expose event metadata, so
  ``tf_ops`` decodes it from the raw ``XSpace`` bytes with a protobuf
  wire-format walk;
* host spans named ``graph.<call>`` and ``graph.<call>.<phase>``
  (``jax.profiler.TraceAnnotation``), on the host plane beside the
  benchmark's.

``ScopedTrace`` is a ``TraceSummary`` that also holds both, so that
``idle_gaps`` labels each gap by the innermost span of either kind, and
adds ``scope_seconds`` and ``idle_seconds_in``. Importing this module
makes ``tracereduce.load`` return a ``ScopedTrace``; every number that
``TraceSummary`` computes stays what it was. On a program without scopes
or spans the additions find nothing, and the readers that use them
return None.
"""
from __future__ import annotations

import gzip
import pathlib

import numpy as np

import tracereduce

PROGRAM_SPAN_PREFIX = "graph."

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_NAME = 2
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    varints, a (start, end) span for length-delimited fields; fixed-width
    fields are skipped."""
    i = lo
    while i < hi:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == _VARINT:
            v, i = _varint(buf, i)
            yield tag >> 3, v
        elif kind == _LEN:
            n, i = _varint(buf, i)
            yield tag >> 3, (i, i + n)
            i += n
        elif kind == _I64:
            i += 8
        elif kind == _I32:
            i += 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, span):
    """(key, value span) of one map<int64, message> entry."""
    key, val = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == _MAP_KEY:
            key = v
        elif f == _MAP_VALUE:
            val = v
    return key, val


def tf_ops(raw: bytes) -> dict:
    """The ``tf_op`` stat of every device plane's event metadata, keyed by
    the metadata's name, which is the event name ``ProfileData`` gives."""
    buf = memoryview(raw)
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == _PLANE_NAME:
                name = _text(buf, v)
            elif g == _PLANE_EVENT_META:
                events.append(_map_entries(buf, v)[1])
            elif g == _PLANE_STAT_META:
                key, meta = _map_entries(buf, v)
                for h, w in _fields(buf, *meta):
                    if h == _STAT_META_NAME:
                        stat_names[key] = _text(buf, w)
        if not tracereduce._DEVICE.match(name):
            continue
        tf_id = [k for k, n in stat_names.items() if n == "tf_op"]
        if not tf_id:
            continue
        for meta in events:
            ev_name, path = None, None
            for g, v in _fields(buf, *meta):
                if g == _EVENT_META_NAME:
                    ev_name = _text(buf, v)
                elif g == _EVENT_META_STATS:
                    stat = dict(_fields(buf, *v))
                    if stat.get(_STAT_META_ID) != tf_id[0]:
                        continue
                    if _STAT_STR in stat:
                        path = _text(buf, stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        path = stat_names.get(stat[_STAT_REF])
            if ev_name is not None and path:
                out.setdefault(ev_name, path)
    return out


def _busy_before(busy: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy time before each instant ``t``, of sorted disjoint ``busy``."""
    if len(busy) == 0:
        return np.zeros_like(t)
    starts, lens = busy[:, 0], busy[:, 1] - busy[:, 0]
    cum = np.concatenate([[0.0], np.cumsum(lens)])
    k = np.searchsorted(starts, t, side="right")
    j = np.clip(k - 1, 0, len(busy) - 1)
    part = np.clip(t - starts[j], 0.0, lens[j])
    return np.where(k > 0, cum[j] + part, 0.0)


class ScopedTrace(tracereduce.TraceSummary):
    """A ``TraceSummary`` with the ops' scope paths (``tf_op``) and the
    program's ``graph.*`` host spans."""

    def __init__(self, pdata, raw: bytes):
        super().__init__(pdata)
        self.tf_op = tf_ops(raw)
        self.program_spans = [
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in pdata.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_SPAN_PREFIX)]
        self.spans = self.spans + self.program_spans

    def scope_seconds(self, scope: str | None, module: str) -> float:
        """Device seconds, per chip, of the ops inside executions of the
        program ``module`` whose ``tf_op`` path has ``scope`` as one of its
        components (every op when ``scope`` is None). Loops and
        conditionals are left out, as in ``top_ops``: their bodies' ops
        are counted."""
        total = 0.0
        for d in self.devices:
            inside = self._inside(d, module)
            for o in d["ops"]:
                if tracereduce._CONTAINER.match(o[2]):
                    continue
                if scope is not None and scope not in \
                        self.tf_op.get(o[2], "").split("/"):
                    continue
                if inside(o):
                    total += o[1] - o[0]
        return self._per_chip(total * 1e-9)

    def has_spans(self, names) -> bool:
        return any(s[2] in names for s in self.spans)

    def idle_seconds_in(self, names) -> float:
        """Device idle seconds, per chip, inside the window and inside any
        host span named in ``names``."""
        iv = tracereduce._merge(np.asarray(
            [(max(s, self.t0), min(e, self.t1)) for s, e, n in self.spans
             if n in names and e > self.t0 and s < self.t1],
            np.float64).reshape(-1, 2))
        if not len(iv):
            return 0.0
        total = 0.0
        for d in self.devices:
            busy = (_busy_before(d["busy"], iv[:, 1]) -
                    _busy_before(d["busy"], iv[:, 0]))
            total += float(np.sum(iv[:, 1] - iv[:, 0] - busy))
        return self._per_chip(total * 1e-9)


def scope_ms_per_execution(ctx, scope: str, module: str):
    """Device ms of ``scope`` per execution of ``module`` in the traced
    window, or None when the trace has no such scope."""
    t = ctx.trace
    if not isinstance(t, ScopedTrace) or not t.module_count(module):
        return None
    s = t.scope_seconds(scope, module)
    return 1e3 * s / t.module_count(module) if s else None


def raw_bytes(path) -> bytes:
    p = pathlib.Path(path)
    if p.is_dir():
        found = sorted(p.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = found[-1]
    raw = p.read_bytes()
    return gzip.decompress(raw) if p.suffix == ".gz" else raw


def load(path) -> ScopedTrace:
    """``tracereduce.load`` that returns a ``ScopedTrace``."""
    from jax.profiler import ProfileData
    raw = raw_bytes(path)
    return ScopedTrace(ProfileData.from_serialized_xspace(raw), raw)


tracereduce.load = load
