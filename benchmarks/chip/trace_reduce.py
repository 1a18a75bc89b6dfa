"""From a profiler trace of the window to the numbers the readers report.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.  On a
TPU each chip is a plane ``/device:TPU:<k>`` whose ``XLA Ops`` line holds one
event per operation run and whose ``XLA Modules`` line one event per compiled
program run; host threads are ``/host:...`` planes, where the benchmark's
``chipbench.window`` annotation marks the window on the trace's clock.

* busy: the union of a chip's operation intervals inside the window,
  averaged over the chips;
* idle gaps: the rest of the window, each piece labelled with the innermost
  ``repro.obs`` span open on the host at the time (the spans are moved onto
  the trace's clock by the window annotation);
* device operations: each operation's self time (its time less that of the
  operations nested in it, such as a loop's body), summed by name and
  averaged over the chips; a name is the HLO instruction's (``%while.37``);
* module time and runs: the summed time and the number of runs of named XLA
  modules (``jit_one``, the name before the compile hash), averaged over the
  chips.

:func:`reduce_events` works on plain lists, so it is tested without a chip;
:func:`reduce_profile` reads the files and calls it.
"""

from __future__ import annotations

from pathlib import Path

NO_SPAN = "host outside any span"


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` pieces of ``intervals`` clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def complement(pieces, lo: float, hi: float) -> list[tuple[float, float]]:
    gaps, t = [], lo
    for s, e in pieces:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(spans) -> list[tuple[float, float, str]]:
    """Flatten nested ``(start, end, name)`` host spans into pieces labelled
    with the innermost span open over each."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    pieces: list[tuple[float, float, str]] = []
    stack: list[tuple[float, float, str]] = []
    t = None

    def emit(until: float) -> None:
        nonlocal t
        if t is None or until > t:
            if stack and t is not None:
                pieces.append((t, until, stack[-1][2]))
            t = until

    for s, e, name in spans:
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((s, e, name))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return pieces


def label_gaps(gaps, pieces) -> dict[str, float]:
    """Seconds of ``gaps`` under each label of ``pieces``; the rest under
    :data:`NO_SPAN`."""
    out: dict[str, float] = {}
    k = 0  # both lists are sorted and non-overlapping: one pass over each
    for g0, g1 in gaps:
        while k < len(pieces) and pieces[k][1] <= g0:
            k += 1
        covered, j = 0.0, k
        while j < len(pieces) and pieces[j][0] < g1:
            s, e, name = pieces[j]
            d = min(e, g1) - max(s, g0)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                covered += d
            j += 1
        if g1 - g0 - covered > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (g1 - g0 - covered)
    return out


def self_times(events) -> dict[str, float]:
    """Each name's time less that of the events nested inside it."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, self time]

    def close() -> None:
        end, name, own = stack.pop()
        out[name] = out.get(name, 0.0) + own

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close()
    return out


def reduce_events(devices: dict, window: tuple[float, float], host_spans=(),
                  module_names=()) -> dict:
    """``devices``: chip name to ``{"ops": [(name, start, end)], "modules":
    [(name, start, end)]}``, times in seconds on the trace's clock;
    ``window``: its ``(start, end)``; ``host_spans``: ``(start, end, name)``."""
    lo, hi = window
    n = max(len(devices), 1)
    pieces = innermost(host_spans)
    busy, ops, idle, modules, runs = 0.0, {}, {}, {}, {}
    for lines in devices.values():
        events = lines["ops"] or lines["modules"]
        merged = union([(s, e) for _, s, e in events], lo, hi)
        busy += sum(e - s for s, e in merged) / n
        inside = [(name, max(s, lo), min(e, hi)) for name, s, e in lines["ops"]
                  if min(e, hi) > max(s, lo)]
        for name, d in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + d / n
        for name, d in label_gaps(complement(merged, lo, hi), pieces).items():
            idle[name] = idle.get(name, 0.0) + d / n
        for name, s, e in lines["modules"]:
            if name in module_names and min(e, hi) > max(s, lo):
                modules[name] = modules.get(name, 0.0) + (e - s) / n
                runs[name] = runs.get(name, 0.0) + 1 / n
    by_time = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])  # noqa: E731
    return {"busy_s": busy, "window_s": hi - lo, "device_ops": by_time(ops),
            "idle_gaps": by_time(idle), "module_s": modules, "module_runs": runs}


def load(trace_dir: Path):
    """``(devices, window)`` of the newest trace under ``trace_dir``, in
    seconds on the trace's clock."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, window = {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    # "%while.37 = (s32[] ...) while(...)" -> "%while.37";
                    # "jit_one(1616...)" -> "jit_one"
                    cut, short = (" = " if key == "ops" else "("), {}
                    events = []
                    for e in line.events:
                        name = e.name
                        if name not in short:
                            short[name] = name.split(cut, 1)[0]
                        events.append((short[name], e.start_ns * 1e-9,
                                       (e.start_ns + e.duration_ns) * 1e-9))
                    lines[key] = events
            if lines["ops"] or lines["modules"]:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "chipbench.window":
                        window = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
    if window is None:
        raise ValueError("the trace holds no chipbench.window annotation")
    return devices, window


def reduce_profile(trace_dir: Path, *, spans, span_origin: float, window_anchor: float,
                   module_names=()) -> dict:
    """Reduce the traced window; ``spans`` are ``repro.obs`` spans whose
    ``wall_t0`` counts from ``span_origin`` on ``time.perf_counter``, and
    ``window_anchor`` is the ``perf_counter`` reading at the window's start."""
    devices, window = load(trace_dir)
    shift = window[0] - window_anchor + span_origin
    host = [(s.wall_t0 + shift, s.wall_t0 + s.wall_dur + shift, s.name) for s in spans]
    return reduce_events(devices, window, host, module_names)
