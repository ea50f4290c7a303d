"""The program's own spans, scopes and counts in a traced run.

A program that traces itself (``repro.obs.spans``) writes ``repro:*``
host spans and named device scopes into the profiler's trace.  The
neutral form of :mod:`benchkit.trace` keeps neither, so this module
reads them from the profile that the traced run left under
``bench/out/trace`` (where ``benchkit.report`` has the drivers write
it), once per file:

    {"program": [[span, start_ns, dur_ns, {metadata}], ...],
     "scoped": {"<device>": [[scope_path, start_ns, dur_ns], ...]}}

``program`` holds the host spans without their prefix, with the
metadata the program gave them (the ``iteration`` step carries the
iteration's ``exact_calls`` and ``approx_passes``); ``scoped`` holds the
operations of each chip that ran under the program's scopes, each named
by those scopes alone, outermost first (``exact_pass/oracle``), as the
operation's ``op_name`` metadata gives them.  The spans come through
``jax.profiler.ProfileData``; an operation's ``op_name`` lives in its
event metadata (the ``tf_op`` stat), which ``ProfileData`` does not
show, so the scopes are read from the same file as protobuf, through a
message that declares just the fields read.  For a program that does
not trace itself, :func:`of` returns None, and so does every reader
built on it.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from typing import Dict, List, Optional

from . import cells
from .trace import OPS_LINE, union

TRACE_DIR = cells.ROOT / "bench" / "out" / "trace"
# The event-metadata stat of a device operation that holds its op_name.
OP_PATH_STAT = b"tf_op"


def names():
    """The program's :mod:`repro.obs.spans`, or None for a program that
    has no spans of its own."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    return spans


def scope_path(op_path: str, scopes) -> Optional[str]:
    """The program's scopes in an ``op_name`` path, outermost first:
    ``jit(f)/exact_pass/while/body/oracle/dot_general:`` ->
    ``exact_pass/oracle``; None when it holds none of ``scopes``."""
    parts = [p for p in op_path.split(":")[0].split("/") if p in scopes]
    return "/".join(parts) if parts else None


def of(ctx: dict) -> Optional[dict]:
    """The program's spans and scopes in the profile of ``ctx``'s traced
    run; None for a run that traced nothing, or a program without
    spans."""
    if not ctx.get("trace") or names() is None:
        return None
    files = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    got = load(path, os.stat(path).st_mtime_ns)
    return got if got["program"] or any(got["scoped"].values()) else None


@functools.lru_cache(maxsize=2)
def load(path: str, mtime_ns: int = 0) -> dict:
    """The program's part of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    del mtime_ns  # part of the cache key only
    sp = names()
    program: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(sp.PREFIX):
                        program.append([ev.name[len(sp.PREFIX):],
                                        float(ev.start_ns),
                                        float(ev.duration_ns),
                                        {k: v for k, v in ev.stats}])
    program.sort(key=lambda s: s[1])
    scopes = (sp.EVICT, sp.EXACT_PASS, sp.ORACLE, sp.APPROX_PASS)
    return {"program": program, "scoped": _scoped(path, scopes)}


def _scoped(path: str, scopes) -> Dict[str, List[list]]:
    """Each chip's operations under the program's scopes, timed as
    ``ProfileData`` times them (whole nanoseconds)."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: Dict[str, List[list]] = {}
    for plane in space.planes:
        if not plane.name.startswith(b"/device:TPU:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_of = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == OP_PATH_STAT:
                    op = st.str_value or stat_names.get(st.ref_value, b"")
                    scope_of[e.key] = scope_path(
                        op.decode("utf-8", "replace"), scopes)
        evs = out[plane.name.decode()] = []
        for line in plane.lines:
            if line.name != OPS_LINE.encode():
                continue
            t0 = line.timestamp_ns
            for ev in line.events:
                path_ = scope_of.get(ev.metadata_id)
                if path_:
                    evs.append([path_, float(t0 + ev.offset_ps // 1000),
                                float(ev.duration_ps // 1000)])
    return out


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The ``XSpace`` message of ``tsl/profiler/protobuf/xplane.proto``,
    declaring just the fields read here; the rest is skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="benchkit_xplane.proto",
                                           package="benchkit_xplane")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            fd = m.field.add(name=fname, number=number,
                             label=(T.LABEL_REPEATED if repeated
                                    else T.LABEL_OPTIONAL))
            if isinstance(ftype, str):
                fd.type, fd.type_name = T.TYPE_MESSAGE, \
                    ".benchkit_xplane." + ftype
            else:
                fd.type = ftype

    message("XStat", ("metadata_id", 1, T.TYPE_INT64, False),
            ("str_value", 5, T.TYPE_BYTES, False),
            ("ref_value", 7, T.TYPE_UINT64, False))
    message("XEventMetadata", ("stats", 5, "XStat", True))
    message("XStatMetadata", ("name", 2, T.TYPE_BYTES, False))
    # map<int64, ...> fields, read as their repeated key-value entries
    message("EventMetadataEntry", ("key", 1, T.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, T.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XEvent", ("metadata_id", 1, T.TYPE_INT64, False),
            ("offset_ps", 2, T.TYPE_INT64, False),
            ("duration_ps", 3, T.TYPE_INT64, False))
    message("XLine", ("name", 2, T.TYPE_BYTES, False),
            ("timestamp_ns", 3, T.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XPlane", ("name", 2, T.TYPE_BYTES, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchkit_xplane.XSpace"))


def spans_named(prog: dict, name: str) -> List[list]:
    """The program's host spans named ``name``."""
    return [s for s in prog["program"] if s[0] == name]


def iterations(prog: dict) -> List[dict]:
    """The metadata of each traced outer iteration."""
    return [s[3] for s in spans_named(prog, names().ITERATION)]


def scope_seconds(prog: dict, outer: str, inner: Optional[str] = None):
    """Device seconds under scope ``outer`` (and ``inner`` inside it),
    averaged over the chips: the union of those operations' intervals,
    so that a loop and its body count once.  None where no operation
    carries the scope."""
    per = []
    for evs in prog["scoped"].values():
        sel = []
        for path, s, d in evs:
            parts = path.split("/")
            if outer in parts and (inner is None
                                   or inner in parts[parts.index(outer):]):
                sel.append([s, s + d])
        if sel:
            per.append(sum(e - s for s, e in union(sel)) / 1e9)
    return sum(per) / len(per) if per else None


def round_waits(prog: dict) -> List[float]:
    """For each serving round that decoded: the seconds of it in its
    ``decode`` and ``sync`` spans."""
    sp = names()
    parts = [s for s in prog["program"] if s[0] in (sp.DECODE, sp.SYNC)]
    starts = [s[1] for s in parts]
    out = []
    for _, s, d, _ in spans_named(prog, sp.ROUND):
        inside = parts[bisect.bisect_left(starts, s):
                       bisect.bisect_right(starts, s + d)]
        if any(p[0] == sp.DECODE for p in inside):
            out.append(sum(p[2] for p in inside) / 1e9)
    return out


def covered_seconds(trace: dict, spans: List[list]) -> float:
    """Seconds of ``spans`` during which an operation ran on the first
    chip of the neutral ``trace``."""
    first = sorted(trace["device"])[0]
    busy = union([[e[1], e[1] + e[2]] for e in trace["device"][first]])
    starts = [b[0] for b in busy]
    total = 0.0
    for s in spans:
        t0, t1 = s[1], s[1] + s[2]
        i = max(bisect.bisect_right(starts, t0) - 1, 0)
        while i < len(busy) and busy[i][0] < t1:
            total += max(0.0, min(busy[i][1], t1) - max(busy[i][0], t0))
            i += 1
    return total / 1e9
