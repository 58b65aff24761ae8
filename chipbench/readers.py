"""The generic per-layer readers.  A metric's file (``metrics/<name>.json``)
names one of them, ``"reader": "<function>"``, or a function of another
module of this directory as ``"<module>:<function>"`` (the formulas of
flops_bytes.py; a reader a later PR adds), and its arguments.  A reader
takes the run's facts (the load generator's or the step loop's results under
``end_to_end``, the program's own statistics under ``stats``, the trace's
reduction under ``trace``, the fullest chip's ``memory``, the cell's
``config``, ``traffic``, ``chips`` and ``peaks``) and returns a number, or
None where there is nothing to read; the harness then leaves the metric out."""
import re

import numpy as np


def walk(facts, path):
    for key in path:
        if not isinstance(facts, dict) or facts.get(key) is None:
            return None
        facts = facts[key]
    return facts


def stats_path(facts, path, over=None, scale=1.0):
    """The number at ``path`` in the facts, divided by the one at ``over``
    where that is given, times ``scale``."""
    value = walk(facts, path)
    if value is None:
        return None
    if over is not None:
        base = walk(facts, over)
        if not base:
            return None
        value = value / base
    return value * scale


def trace_module(facts, module, percentile=50, scale=1e3):
    """A percentile of the device durations of one compiled program's
    launches on the trace's "XLA Modules" line (milliseconds by default)."""
    durations = walk(facts, ["trace", "modules", module])
    if not durations:
        return None
    return float(np.percentile(durations, percentile)) * scale


def trace_op_share(facts, match):
    """Device time of the operations whose name matches ``match``, as a
    percentage of the device's busy time."""
    ops, busy = walk(facts, ["trace", "ops"]), walk(facts, ["trace", "busy_s"])
    if not ops or not busy:
        return None
    return 100.0 * sum(t for n, t in ops.items() if re.search(match, n)) / busy


def idle_share(facts):
    """The percentage of the traced window in which no operation ran on the
    device, mean over the cell's chips."""
    window, busy = (walk(facts, ["trace", "window_s"]),
                    walk(facts, ["trace", "busy_s"]))
    if not window or not busy:
        return None
    return 100.0 * (1.0 - busy / window)


def memory(facts):
    """Peak bytes on the fullest chip (run.py: the allocator's peak, or the
    bytes held while the window's program ran where the kind knows them)
    over the chip's limit, in per cent."""
    return stats_path(facts, ["memory", "peak_bytes"],
                      over=["memory", "bytes_limit"], scale=100.0)

