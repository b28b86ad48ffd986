"""Device time under some of the program's named scopes over the stretch's
busy time, in per cent.

An event on the device's ops line is named by its HLO line, which holds the
instruction's own name (a Pallas kernel's ``name=``, ``%flash_fwd_win.3``;
XLA's own grouped-product kernel, ``%ragged-dot-none.7``) but NOT the
``jax.named_scope`` the operation sat in: that is in the event's METADATA,
as the stat ``tf_op`` (``jit(step)/transpose(jvp())/.../moe_experts/mul``),
which ``jax.profiler.ProfileData`` does not hand out. ``scopes_of`` reads it
from the ``.xplane.pb`` itself (protobuf wire format: XSpace.planes = 1;
XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5, both maps of key
1 and value 2; XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
.str_value = 5, .ref_value = 7; XStatMetadata.name = 2).

An event belongs to a scope if its instruction's name starts with one of
``ops``, or one of the path components of its ``tf_op`` (inside any
``jvp(...)``/``transpose(...)`` wrapper) starts with one of ``scopes``.
Containers (``while``) are left out, as everywhere. Finds nothing to read
(no trace, no device plane, no event matches) -> reports nothing."""

from __future__ import annotations

import re
import sys
from pathlib import Path

import xplane

TRACE_DIR = Path(__file__).resolve().parents[1] / ".state" / "trace"


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i: i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _entry(buf):
    """(key, value) of a protobuf map's entry."""
    fields = dict(_fields(buf))
    return fields.get(1, 0), fields.get(2, b"")


def scopes_of(path) -> dict:
    """{event name: tf_op} over the device planes of an ``.xplane.pb``."""
    data = memoryview(Path(path).read_bytes())
    out = {}
    for number, plane in _fields(data):
        if number != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_entry(v)[1])
            elif f == 5:
                key, meta = _entry(v)
                stats[key] = next((bytes(x).decode() for g, x in _fields(meta)
                                   if g == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        for meta in events:
            ev_name, tf_op = "", ""
            for f, v in _fields(meta):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    stat = {g: x for g, x in _fields(v)}
                    if stats.get(stat.get(1)) == "tf_op":
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stats.get(stat.get(7), ""))
            if ev_name:
                out[ev_name] = tf_op
    return out


def scope_seconds(ctx, scopes, ops=()):
    """(seconds, events) of the window's device events under ``scopes`` or
    named ``ops``, averaged over chips; ``(0.0, 0)`` where there is no
    trace to read."""
    tr = ctx["trace"]
    if tr is None or not tr.device_ops:
        return 0.0, 0
    try:
        tf_ops = scopes_of(xplane.find_xplane(TRACE_DIR))
    except (FileNotFoundError, ValueError, IndexError):
        tf_ops = {}
    under = re.compile(r"(?:^|[/(])(?:%s)" % "|".join(map(re.escape, scopes)))
    total, n = 0.0, 0
    for s, e, name in tr.ops_in_window():
        if xplane._is_container(name):
            continue
        own = name.split(" = ", 1)[0].lstrip("%")
        if any(own.startswith(p) for p in ops) \
                or under.search(tf_ops.get(name, "")):
            total += min(e, tr.t1) - max(s, tr.t0)
            n += 1
    chips = max(len(tr.device_ops), 1)
    return total / 1e9 / chips, n // chips


def read(ctx, scopes, ops=()):
    seconds, n = scope_seconds(ctx, scopes, ops)
    tr = ctx["trace"]
    if seconds <= 0 or tr.busy_s <= 0:
        return None
    print(f"scope_time_share {scopes}: {n} events, {seconds:.6f} s of "
          f"{tr.busy_s:.6f} s busy", file=sys.stderr)
    return 100.0 * seconds / tr.busy_s
