"""Device time of one name scope of the program, from a profiler trace.

A ``jax.named_scope`` reaches the ``op_name`` metadata of every operation
XLA compiles from inside it (``jit(one)/vmap()/while/body/closed_call/
fitness/...``, or ``.../vmap(fitness)/...`` where the scope sits right under
a transform).  On a TPU the profiler keeps that path as the ``tf_op`` stat
of each ``XLA Ops`` event's metadata.  ``ProfileData`` yields the events but
not their metadata's stats, so :func:`op_names` reads those from the
``.xplane.pb`` file itself: a few fields of the ``XSpace`` message, decoded
from the protobuf wire format (no proto library is needed).

* :func:`in_scope` — whether an ``op_name`` path has the scope's segment;
* :func:`scope_seconds` — self time of the scope's operations inside the
  window, averaged over the chips (plain lists, tested without a chip);
* :func:`traced_scope_seconds` — the same from the newest trace under
  ``harness.TRACE_DIR``, or ``None`` when no operation of the scope is there.
"""

from __future__ import annotations

import re
from pathlib import Path

from trace_reduce import self_times

#: the stat of an op's event metadata that holds its ``op_name``
OP_NAME_STAT = "tf_op"
WINDOW = "chipbench.window"


def in_scope(op_name: str | None, scope: str) -> bool:
    """``scope`` is a segment of the path, bare or as a transform's argument
    (``vmap(fitness)``); a trailing ``:type`` of the stat is ignored."""
    if not op_name:
        return False
    pattern = re.compile(r"(?:[\w.-]+\()*" + re.escape(scope) + r"\)*")
    return any(pattern.fullmatch(seg) for seg in op_name.split(":", 1)[0].split("/"))


def scope_seconds(devices: dict, window: tuple[float, float], names: dict, scope: str) -> float:
    """``devices``: chip plane to ``[(event name, start, end)]`` of its XLA
    operations, in seconds; ``names``: chip plane to ``{event name: set of
    op_name paths}``.  The self time of the operations in ``scope`` inside
    ``window``, averaged over the chips; an event name whose metadata gives
    paths both in and out of the scope counts as out."""
    lo, hi = window
    total = 0.0
    for plane, ops in devices.items():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops if min(e, hi) > max(s, lo)]
        paths = names.get(plane, {})
        for name, seconds in self_times(inside).items():
            found = paths.get(name)
            if found and all(in_scope(p, scope) for p in found):
                total += seconds
    return total / max(len(devices), 1)


# -- the xplane file: just the fields this needs, from the wire format --------


def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """``(field number, value)`` of one message: an int, or a memoryview of
    the bytes of a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_names(path: Path) -> dict[str, dict[str, set[str]]]:
    """Chip plane to ``{event name: set of op_name paths}`` for the events
    whose metadata carries :data:`OP_NAME_STAT`.

    ``XSpace.planes`` = 1; ``XPlane``: name 2, event_metadata 4 (map entry:
    value 2), stat_metadata 5 (map entry: value 2); ``XEventMetadata``:
    name 2, stats 5; ``XStatMetadata``: id 1, name 2; ``XStat``:
    metadata_id 1, str_value 5, ref_value 7 (a stat metadata's name)."""
    data = memoryview(Path(path).read_bytes())
    out: dict[str, dict[str, set[str]]] = {}
    for number, plane in _fields(data):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                meta = dict(_fields(dict(_fields(value)).get(2, b"")))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not name.startswith("/device:"):
            continue
        wanted = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        found: dict[str, set[str]] = {}
        for entry in events:
            meta = dict(_fields(entry)).get(2, b"")
            event_name, paths = "", set()
            for field, value in _fields(meta):
                if field == 2:
                    event_name = _text(value)
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        paths.add(_text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), ""))
            if paths:
                found.setdefault(event_name, set()).update(paths)
        if found:
            out[name] = found
    return out


def load(path: Path) -> tuple[dict, tuple[float, float] | None]:
    """``(devices, window)`` of one trace file: each chip plane's ``XLA Ops``
    events under their full names, and the :data:`WINDOW` annotation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, window = {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
    return devices, window


def traced_scope_seconds(scope: str, trace_dir: Path | None = None) -> float | None:
    """Self time of ``scope``'s operations per chip inside the window of the
    newest trace under ``trace_dir`` (``harness.TRACE_DIR``); ``None`` when
    there is no trace, no window or no operation of the scope."""
    if trace_dir is None:
        import harness

        trace_dir = harness.TRACE_DIR
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    names = op_names(files[-1])
    if not names:
        return None
    devices, window = load(files[-1])
    if window is None:
        return None
    return scope_seconds(devices, window, names, scope) or None
