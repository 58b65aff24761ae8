"""From the JAX profiler's ``.xplane.pb`` to numbers: device busy and idle
time, the durations of each compiled program ("XLA Modules") and each
operation ("XLA Ops"), and the longest idle gaps named by what the host was
doing in them.  Reads the file with jax.profiler.ProfileData alone."""
import collections
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TOP = 10            # entries of each list of the breakdown
GAPS_NAMED = 200    # the longest gaps are named; the rest stay "not_named"


def start(trace_dir):
    """Start the profiler without the Python tracer: 24 client threads
    under it would slow the host and swell the trace."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop():
    import jax
    jax.profiler.stop_trace()


def short(name):
    """An event's name as a breakdown entry: the first 80 safe characters,
    which for an HLO operation hold its own name and its result's shape."""
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", name).strip("_")[:80]


def union(starts, ends):
    """Merged intervals of (starts, ends), as two sorted arrays."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts)
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    reach = np.maximum.accumulate(e)
    first = np.concatenate(([True], s[1:] > reach[:-1]))
    last = np.concatenate((first[1:], [True]))
    return s[first], reach[last]


def events(line):
    """(names, starts, ends) of a line's events, in seconds."""
    evs = list(line.events)
    starts = np.array([e.start_ns for e in evs], float) * 1e-9
    ends = starts + np.array([e.duration_ns for e in evs], float) * 1e-9
    return [e.name for e in evs], starts, ends


def reduce(trace_dir, chips):
    """Reduce the newest trace under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError("the profiler wrote no trace under %s" % trace_dir)
    return reduce_file(paths[-1], chips)


def reduce_file(path, chips):
    """The facts the per-layer readers take from one trace:

    window_s    first event's start to last event's end, over all planes
    busy_s      seconds in which an operation ran, mean over ``chips`` devices
    modules     {program name: [device seconds of each launch]}, device 0
    ops         {operation name: device seconds in all}, device 0; those of
                the asynchronous line (copies and collectives in flight
                beside other operations) are prefixed ``async_``
    device_ops  the TOP operations of ``ops``, [[name, seconds]]
    idle_gaps   device 0's idle seconds by the innermost host event that
                covers the middle of each gap, the TOP of them
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, t_min, t_max = {}, [], np.inf, -np.inf
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            names, starts, ends = events(line)
            if not names:
                continue
            t_min, t_max = min(t_min, starts.min()), max(t_max, ends.max())
            if m:
                devices.setdefault(int(m.group(1)), {})[line.name] = (
                    names, starts, ends)
            elif plane.name == "/host:CPU":
                host.append((names, starts, ends))
    out = {"window_s": float(max(t_max - t_min, 0.0)) if host or devices
           else 0.0, "busy_s": 0.0, "modules": {}, "ops": {},
           "device_ops": [], "idle_gaps": []}
    used = sorted(devices)[:chips]
    if not used:
        return out

    busy = {}
    for d in used:
        lines = devices[d]
        _, s, e = lines.get("XLA Ops") or lines.get("XLA Modules") or (
            [], np.zeros(0), np.zeros(0))
        busy[d] = union(s, e)
    out["busy_s"] = float(np.mean([(e - s).sum() for s, e in busy.values()]))

    lines = devices[used[0]]
    names, s, e = lines.get("XLA Modules", ([], np.zeros(0), np.zeros(0)))
    for n, dur in zip(names, e - s):
        out["modules"].setdefault(n.split("(", 1)[0], []).append(float(dur))
    ops = collections.Counter()
    for key, prefix in (("XLA Ops", ""), ("Async XLA Ops", "async_")):
        names, s, e = lines.get(key, ([], np.zeros(0), np.zeros(0)))
        for n, dur in zip(names, e - s):
            ops[short(prefix + n)] += float(dur)
    out["ops"] = dict(ops)
    out["device_ops"] = [[n, t] for n, t in ops.most_common(TOP)]

    bs, be = busy[used[0]]
    gap_s = np.concatenate(([t_min], be))
    gap_e = np.concatenate((bs, [t_max]))
    length = gap_e - gap_s
    h_names = [n for names, _, _ in host for n in names]
    h_s = np.concatenate([s for _, s, _ in host]) if host else np.zeros(0)
    h_e = np.concatenate([e for _, _, e in host]) if host else np.zeros(0)
    h_len = h_e - h_s
    named = collections.Counter()
    order = np.argsort(-length)
    for g in order[:GAPS_NAMED]:
        if length[g] <= 0:
            break
        mid = 0.5 * (gap_s[g] + gap_e[g])
        cover = np.flatnonzero((h_s <= mid) & (h_e >= mid))
        who = (short(h_names[cover[np.argmin(h_len[cover])]])
               if len(cover) else "no_host_span")
        named[who] += float(length[g])
    rest = float(length[order[GAPS_NAMED:]].clip(min=0).sum())
    if rest > 0:
        named["not_named"] += rest
    out["idle_gaps"] = [[n, t] for n, t in named.most_common(TOP)]
    return out
