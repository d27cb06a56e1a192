"""From the profiler's trace to numbers: device busy and idle time, time
by device operation, and idle gaps named by what the host was doing.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` with nothing but
JAX. ``extract`` turns it into plain tuples, and everything after that is
arithmetic on ``(name, start_ns, duration_ns)``, checked on a synthetic
trace in ``benchmark/tests/test_trace_reduce.py``.
"""

import glob
import os
import re

WINDOW_SPAN = "bench_window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# the kind ``parse_op`` gives a Pallas kernel's call
PALLAS_CALL = "custom-call:tpu_custom_call"


_HLO = re.compile(r"^%?([^\s=]+) = (.*)$", re.S)
_OPCODE = re.compile(r"[\]\})]\s([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(event_name):
    """The device line names an operation by its whole HLO text:
    ``%jvp__.12 = bf16[...] custom-call(...), custom_call_target="tpu_custom_call"``.
    Returns ``("jvp__.12", "custom-call:tpu_custom_call")``; a name that
    is no HLO text is returned as it is, with kind ``""``."""
    m = _HLO.match(event_name)
    if not m:
        return event_name, ""
    name, rest = m.groups()
    op = _OPCODE.search(rest)
    kind = op.group(1) if op else ""
    if kind == "custom-call":
        target = _TARGET.search(rest)
        kind += ":" + (target.group(1) if target else "")
    return name, kind


def base_name(name):
    """``convert_reduce_fusion.54`` -> ``convert_reduce_fusion``."""
    return re.sub(r"[.\d]+$", "", name) or name


def load(trace_dir):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return ProfileData.from_file(files[-1])


def extract(profile, host_span_names):
    """``{"devices": {plane: [(name, start, dur)]}, "host": [...],
    "kinds": {name: kind}}``: each device plane's operations under their
    short names, and the host's spans of those names."""
    devices, host, kinds = {}, [], {}
    parsed = {}     # a step's operations come again every step: parse once
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OP_LINE:
                    events = []
                    for e in line.events:
                        text = e.name
                        if text not in parsed:
                            parsed[text] = parse_op(text)
                            kinds[parsed[text][0]] = parsed[text][1]
                        events.append((parsed[text][0], e.start_ns,
                                       e.duration_ns))
                    devices[plane.name] = events
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name in host_span_names)
    return {"devices": devices, "host": host, "kinds": kinds}


def clip(events, t0, t1):
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def merged(events):
    """The union of the events' intervals, as sorted ``[start, end]``."""
    out = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return out


def busy_ns(events):
    return sum(b - a for a, b in merged(events))


def op_totals(events):
    """``{name: [nanoseconds, calls]}``."""
    out = {}
    for name, _, dur in events:
        t = out.setdefault(name, [0.0, 0])
        t[0] += dur
        t[1] += 1
    return out


def gaps(events, t0, t1):
    """The intervals of ``[t0, t1]`` in which no event runs."""
    out, at = [], t0
    for a, b in merged(events):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def name_gap(gap, host):
    """The host span that covers most of the gap; the innermost where
    spans nest (the shortest of those that cover equally)."""
    a, b = gap
    best, best_key = "host_other", (0.0, 0.0)
    for name, start, dur in host:
        cover = min(b, start + dur) - max(a, start)
        if cover > 0 and (cover, -dur) > best_key:
            best, best_key = name, (cover, -dur)
    return best


def _by_kind(totals, kinds):
    out = {}
    for name, (ns, _) in totals.items():
        kind = kinds.get(name, "")
        out[kind] = out.get(kind, 0.0) + ns * 1e-9
    return out


def summarize(data, chips, inner_spans=("dispatch", "fetch_loss",
                                        "next_feed", "drain")):
    """``data`` is a ProfileData or what ``extract`` gives. The window is
    the host's ``bench_window`` span; device numbers are averaged over
    the ``chips`` planes with most work in it. ``device_ops`` groups the
    operations by name without their number (one entry for the twelve
    layers' ``fusion.N``)."""
    if not isinstance(data, dict):
        data = extract(data, (WINDOW_SPAN,) + tuple(inner_spans))
    spans = [e for e in data["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no %r span" % WINDOW_SPAN)
    _, t0, dur = max(spans, key=lambda e: e[2])
    t1 = t0 + dur
    host = [e for e in data["host"] if e[0] != WINDOW_SPAN]
    planes = sorted(((busy_ns(ev), name, ev) for name, ev in (
        (n, clip(ev, t0, t1)) for n, ev in data["devices"].items())),
        reverse=True)[:chips]
    if not planes or planes[0][0] <= 0:
        raise ValueError("no operation ran on a device inside the window")
    n = len(planes)
    totals = {}
    for _, _, ev in planes:
        for name, (ns, calls) in op_totals(ev).items():
            t = totals.setdefault(name, [0.0, 0])
            t[0] += ns / n
            t[1] += calls
    first = planes[0][2]
    idle = sorted(((b - a, name_gap((a, b), host))
                   for a, b in gaps(first, t0, t1)), reverse=True)
    idle_by_span = {}
    for ns, name in idle:
        idle_by_span[name] = idle_by_span.get(name, 0.0) + ns * 1e-9
    groups = {}
    for name, (ns, _) in totals.items():
        groups[base_name(name)] = groups.get(base_name(name), 0.0) + ns
    ranked = sorted(groups.items(), key=lambda kv: -kv[1])
    kinds = data.get("kinds", {})
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(b for b, _, _ in planes) / n * 1e-9,
        "op_seconds": {k: v[0] * 1e-9 for k, v in totals.items()},
        "op_calls": {k: v[1] for k, v in totals.items()},
        "op_kinds": {k: kinds.get(k, "") for k in totals},
        "kind_seconds": _by_kind(totals, kinds),
        "device_ops": [[k, ns * 1e-9] for k, ns in ranked[:10]],
        "idle_gaps": [[name, ns * 1e-9] for ns, name in idle[:10]],
        "idle_by_span": idle_by_span,
        "planes": [name for _, name, _ in planes],
    }
