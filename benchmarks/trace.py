"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU the planes
``/device:TPU:<n>`` carry one line ``XLA Ops`` whose events are the
operations as the device ran them (start and duration in nanoseconds on
the trace's clock); ``/host:CPU`` carries one line per host thread, with
the program's ``TraceAnnotation`` / ``StepTraceAnnotation`` spans on the
same clock (the Python tracer's own frames start with ``$`` and are
skipped).

``reduce`` gives, averaged over the chips used:

``busy_s``      the union of the intervals in which an operation ran;
``window_s``    first operation's start to last operation's end;
``op_seconds``  device seconds by operation name: each operation's SELF
                time, its span less the operations nested inside it (a
                ``while`` spans its whole body), so the names add up to
                ``busy_s``; a name is the HLO instruction's own name and
                opcode (``%fusion.12 fusion f32[8,128]``), not its whole text;
``opcode_seconds``  the same seconds by HLO opcode (``custom-call`` is
                where the Pallas kernels are), the ten largest;
``device_ops``  the ten names that took most device time;
``idle_gaps``   the idle seconds between operations, by the innermost
                host span that covers each gap's midpoint (the ten
                largest; ``(no span)`` where none does).

Checked on a recorded trace in ``benchmarks/tests/test_trace.py``."""

import re

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def _intervals(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def merge(intervals):
    """Sorted, merged (start, end) pairs of possibly nested or
    overlapping intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def short_name(name):
    """``%f.23 = (bf16[192,1024,64]{2,1,0:T(8,128)}, ...) custom-call(...)``
    -> ``%f.23 custom-call (bf16[192,1024,64], ...)``: the instruction,
    its opcode and the type of its result without the layouts; a name
    that is no HLO text stays as it is (cut at 120)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    depth = 0
    for i, ch in enumerate(rest):      # the result's type ends at the
        if ch in "([{":                # first space outside brackets
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            result = re.sub(r"\{[^{}]*\}|/\*[^*]*\*/", "", rest[:i])
            return ("%s %s %s" % (head, rest[i + 1:].split("(", 1)[0],
                                  result))[:120]
    return head[:120]


def self_seconds(events):
    """name -> nanoseconds of SELF time over ``(name, start, end)``
    events of one line, where an event may enclose others."""
    out, stack = {}, []

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, end, inner = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start) - inner
            if stack:
                stack[-1][3] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start, end, 0.0])
    close(float("inf"))
    return out


def host_spans(profile, host_plane=HOST_PLANE):
    """(start, end, name) of every annotation span on the host plane."""
    spans = []
    for plane in profile.planes:
        if plane.name != host_plane:
            continue
        for line in plane.lines:
            for name, start, end in _intervals(line):
                if end > start and not name.startswith("$") \
                        and "::" not in name:
                    spans.append((start, end, name))
    spans.sort()
    return spans


def enclosing_spans(spans, times):
    """For each time of the sorted list ``times``: the name of the
    innermost span (the one that started last) covering it; ``(no
    span)`` where none does.  ``spans`` is sorted by start."""
    out, active, at = [], [], 0
    for t in times:
        while at < len(spans) and spans[at][0] <= t:
            active.append(spans[at])
            at += 1
        active = [sp for sp in active if sp[1] > t]
        out.append(max(active)[2] if active else "(no span)")
    return out


def reduce_profile(profile, chips=1, device_plane=DEVICE_PLANE,
                   op_line=OP_LINE, host_plane=HOST_PLANE):
    planes = [p for p in profile.planes if p.name.startswith(device_plane)]
    planes.sort(key=lambda p: p.name)
    spans = host_spans(profile, host_plane)
    busy = window = 0.0
    op_seconds, gaps = {}, {}
    used = 0
    for plane in planes:
        events = [ev for line in plane.lines if line.name.startswith(op_line)
                  for ev in _intervals(line)]
        if not events:
            continue
        used += 1
        for name, ns in self_seconds(events).items():
            name = short_name(name)
            op_seconds[name] = op_seconds.get(name, 0.0) + ns
        merged = merge((s, e) for _, s, e in events)
        busy += sum(e - s for s, e in merged)
        window += merged[-1][1] - merged[0][0]
        idle = [(end, start) for (_, end), (start, _)
                in zip(merged, merged[1:])]
        names = enclosing_spans(spans, [(a + b) / 2 for a, b in idle])
        for name, (end, start) in zip(names, idle):
            gaps[name] = gaps.get(name, 0.0) + (start - end)
    if not used:
        raise RuntimeError(
            "no device operations in the trace (planes: %s)"
            % [p.name for p in profile.planes])
    if used < chips:
        raise RuntimeError("trace holds %d device planes with operations, "
                           "the cell uses %d chips" % (used, chips))

    def top(d):
        return [[k, v / 1e9 / used] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    by_opcode = {}
    for name, ns in op_seconds.items():
        opcode = name.split(" ")[1] if " " in name else "(other)"
        by_opcode[opcode] = by_opcode.get(opcode, 0.0) + ns
    return {"busy_s": busy / 1e9 / used, "window_s": window / 1e9 / used,
            "op_seconds": {k: v / 1e9 / used for k, v in op_seconds.items()},
            "opcode_seconds": dict(top(by_opcode)),
            "device_ops": top(op_seconds), "idle_gaps": top(gaps)}


def reduce(path, chips=1, **names):
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path),
                          chips=chips, **names)

