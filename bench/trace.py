"""Device trace: capture around the window, and its reduction to numbers.

``Capture`` runs JAX's profiler over the measured window (Python tracer
off, host runtime events on).  ``load`` reads the ``.xplane.pb`` the
profiler writes into a compact table of events: the device's operations
(the ``XLA Ops`` line of every accelerator plane) and the host's spans (our
``TraceAnnotation``s and JAX's runtime events), each as
``[name, text, start_ns, duration_ns]`` where ``text`` holds the event's
string statistics (the HLO op's long name, the kernel name...).  ``reduce``
turns such a table into what the per-layer readers and the result's
``device`` and ``breakdown`` need.  The table is what a test reads from the
small trace recorded on the chip and kept in ``bench/tests/data``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile

OPS_LINE = "XLA Ops"
#: HLO ops that contain others on the same line (the layer scan's while
#: loop holds every op of its body): left out of per-op time, which would
#: count their children twice
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")


class Capture:
    """Profiler on between ``start`` and ``stop``; the raw files live in a
    temporary directory that ``close`` removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> str:
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        return found[0]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _text(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if isinstance(v, str) and v and k not in ("_ct", "_pt"):
            parts.append(f"{k}={v}")
    return ";".join(parts)[:400]


def load(path: str) -> dict:
    """{"ops": [[name, text, start, dur, device] ...], "host": [[name, "",
    start, dur] ...], "devices": n} with times in ns on the profile's
    common clock."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops, host, devices = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = [l for l in plane.lines if l.name == OPS_LINE]
            for line in lines:
                for ev in line.events:
                    ops.append([ev.name, _text(ev), float(ev.start_ns),
                                float(ev.duration_ns), devices])
            devices += bool(lines)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append([ev.name, "", float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"ops": ops, "host": host, "devices": devices}


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float               # union of device-op time, mean over devices
    op_s: dict                  # op name -> summed device seconds
    top_ops: list               # [[name, seconds]] largest ten
    idle_gaps: list             # [[what the host did, seconds]] longest ten
    ops: list                   # [(name, text, seconds)] inside the window
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, pattern: str) -> tuple[float, int]:
        """(summed device seconds, number of events) of the ops in the
        window whose name or text matches ``pattern`` (a regular
        expression), per device."""
        rx = re.compile(pattern)
        hits = [sec for name, text, sec in self.ops
                if rx.search(name) or rx.search(text)]
        return sum(hits) / max(self.devices, 1), len(hits)


def busy_by_device(table: dict, window_ns: tuple, keep=None) -> list:
    """Seconds of each device inside ``window_ns`` in which one of its ops
    ran (the union of their intervals, whatever nests in what); with
    ``keep``, only the ops whose name ``keep(name)`` accepts."""
    w0, w1 = window_ns
    spans: list = [[] for _ in range(max(table["devices"], 1))]
    for n, _, s, d, dev in table["ops"]:
        if s + d > w0 and s < w1 and (keep is None or keep(n)):
            spans[dev].append((max(s, w0), min(s + d, w1)))
    return [sum(e - s for s, e in _union(iv)) / 1e9 for iv in spans]


def reduce(table: dict, window_ns: tuple) -> Reduction:
    """Busy time, per-op time and the longest idle gaps inside
    ``window_ns = (start, end)`` on the profile's clock."""
    w0, w1 = window_ns
    ops = [(s, s + d, n, t, dev) for n, t, s, d, dev in table["ops"]
           if s + d > w0 and s < w1]
    ndev = max(table["devices"], 1)
    busy = sum(busy_by_device(table, window_ns)) / ndev
    # idle gaps of the first device, labelled with what the host was doing
    merged = _union([(max(s, w0), min(e, w1))
                     for s, e, _, _, k in ops if k == 0])
    op_s: dict = {}
    for s, e, n, _, _ in ops:
        if not CONTAINER.match(n):
            key = short_name(n)
            op_s[key] = op_s.get(key, 0.0) + (e - s) / 1e9 / ndev
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    labelled = [[_host_doing(table["host"], (a + b) / 2), (b - a) / 1e9]
                for a, b in gaps]
    return Reduction((w1 - w0) / 1e9, busy, op_s,
                     [[n, s] for n, s in top], labelled,
                     [(n, t, (e - s) / 1e9) for s, e, n, t, _ in ops],
                     table["devices"])


HLO = re.compile(r"^(%\S+) = (.*?) ([a-z][\w-]*)\(")


def short_name(hlo: str) -> str:
    """An op's name for the breakdown: the HLO instruction's name, opcode
    and the start of the type it produces (the trace names each op by its
    whole HLO text)."""
    m = HLO.match(hlo)
    if not m:
        return hlo[:100]
    return f"{m.group(1)} {m.group(3)} {m.group(2)[:60]}"


def _host_doing(host: list, t: float) -> str:
    """The innermost (shortest) host span that covers time t."""
    best = None
    for name, _, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no host span"


def window_on_profile_clock(table: dict, annotation: str) -> tuple:
    """(start, end) of the host span named ``annotation``: the measured
    window as the profiler saw it."""
    spans = [(s, s + d) for n, _, s, d in table["host"] if n == annotation]
    if not spans:
        raise ValueError(f"no host span {annotation!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)
