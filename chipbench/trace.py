"""From a profiler trace to device busy time, kernel time and idle gaps.

`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
operations (planes ``/device:TPU:<n>``, line ``XLA Ops``) and the host's
spans on the Python thread (the harness's ``TraceAnnotation``s and what
JAX records around dispatch), each span with its arguments as tags. All
times are nanoseconds on the trace's one clock. The reductions below
work on plain `Event` lists, so they are checked on synthetic traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: the Python main thread's line is "python" or "python3"
HOST_LINE = "python"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    #: a device op's stats that name it (hlo_module, hlo_op, long_name,
    #: tf_op); a host span's arguments, every one, as strings
    tags: Tuple[Tuple[str, str], ...] = ()
    device: int = 0

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def tag(self, key: str) -> str:
        for k, v in self.tags:
            if k == key:
                return v
        return ""

    def text(self) -> str:
        """Everything that names this event, for matching."""
        return " ".join([self.name] + [v for _, v in self.tags])


@dataclasses.dataclass
class Trace:
    ops: List[Event]  # device operations, all devices
    host: List[Event]  # spans on the host's Python thread
    n_devices: int
    #: device programs (``jit_<function>(<id>)``), each spanning its ops
    modules: List[Event] = dataclasses.field(default_factory=list)

    def device_ops(self, device: int) -> List[Event]:
        return [e for e in self.ops if e.device == device]


_TAGS = ("hlo_module", "hlo_op", "long_name", "tf_op", "name")


def _stats(ev, keep=_TAGS) -> Tuple[Tuple[str, str], ...]:
    """The event's stats named in ``keep`` (all where it is None), as
    strings."""
    try:
        items = list(ev.stats)
    except (AttributeError, TypeError):
        return ()
    return tuple((str(k), str(v)) for k, v in items
                 if keep is None or k in keep)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, device_plane: str = DEVICE_PLANE,
         ops_line: str = OPS_LINE) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[Event] = []
    modules: List[Event] = []
    host: List[Event] = []
    devices = set()
    for plane in pd.planes:
        if plane.name.startswith(device_plane):
            suffix = plane.name[len(device_plane):]
            if not suffix.isdigit():
                continue
            dev = int(suffix)
            devices.add(dev)
            for line in plane.lines:
                into = {ops_line: ops, MODULES_LINE: modules}.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    into.append(Event(ev.name, float(ev.start_ns),
                                      float(ev.duration_ns), _stats(ev),
                                      dev))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if not line.name.startswith(HOST_LINE):
                    continue
                for ev in line.events:
                    host.append(Event(ev.name, float(ev.start_ns),
                                      float(ev.duration_ns),
                                      _stats(ev, None)))
    return Trace(ops, host, len(devices), modules)


def describe(path: str, per_line: int = 5) -> List[str]:
    """Plane, line and a few event names with their stats: what to read
    before trusting the names the reductions match."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                try:
                    st = {k: str(v)[:120] for k, v in ev.stats}
                except (AttributeError, TypeError):
                    st = {}
                out.append(f"    {ev.name[:120]!r} dur_ns={ev.duration_ns}"
                           f" {st}")
    return out


# -- reductions -------------------------------------------------------------

def union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for s, e in sorted((ev.start_ns, ev.end_ns) for ev in events):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran on a device, averaged over the
    devices."""
    if trace.n_devices == 0:
        return 0.0
    return sum(union_ns(trace.device_ops(d)) for d in
               sorted({e.device for e in trace.ops})) / 1e9 / trace.n_devices


def _program_of(trace: Trace) -> Callable[[Event], str]:
    """Maps a device operation to the program it ran in (the
    ``jit_<function>`` of the ``XLA Modules`` span that holds it), or
    "" where no span holds it."""
    import bisect
    spans = sorted((m.device, m.start_ns, m.end_ns, m.name.split("(")[0])
                   for m in trace.modules)
    starts = [(d, s) for d, s, _, _ in spans]

    def program(e: Event) -> str:
        i = bisect.bisect_right(starts, (e.device, e.start_ns)) - 1
        if i >= 0:
            d, s, end, name = spans[i]
            if d == e.device and s <= e.start_ns and e.end_ns <= end:
                return name
        return ""
    return program


def kernel_ops(trace: Trace, kernel: str) -> List[Event]:
    """The device operations of the Pallas kernel whose function is
    ``kernel`` (e.g. ``kv_restore_pallas``): the custom calls that run
    inside the jitted wrapper's program ``jit_<kernel>``, not the small
    copies around them."""
    program = _program_of(trace)
    want = f"jit_{kernel}"
    return [e for e in trace.ops
            if ("custom-call" in e.name or "custom_call" in e.name)
            and program(e) == want]


def kernel_seconds(trace: Trace, kernel: str) -> float:
    """Summed device time of the kernel's operations (`kernel_ops`)."""
    return sum(e.dur_ns for e in kernel_ops(trace, kernel)) / 1e9


def op_name(e: Event) -> str:
    """An operation's instruction name: ``%copy.3 = bf16[...] copy(...)``
    gives ``%copy.3``."""
    return e.name.split(" = ", 1)[0]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time, as
    [["<program>:<instruction>", seconds], ...], seconds averaged over
    devices."""
    program = _program_of(trace)
    by: Dict[str, float] = {}
    for e in trace.ops:
        key = f"{program(e)}:{op_name(e)}"
        by[key] = by.get(key, 0.0) + e.dur_ns
    nd = max(trace.n_devices, 1)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / nd] for k, v in top]


def idle_gaps(trace: Trace, window: Tuple[float, float],
              n: int = 10, device: int = 0) -> List[List]:
    """The ``n`` longest stretches of ``window`` (ns) with no operation
    on ``device``, each named by the innermost host span covering its
    middle (``"<no host span>"`` where none does), as
    [[label, seconds], ...]."""
    lo, hi = window
    gaps = []
    cur = lo
    for s, e in sorted((ev.start_ns, ev.end_ns)
                       for ev in trace.device_ops(device)):
        if s > cur:
            gaps.append((max(cur, lo), min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps = [(a, b) for a, b in gaps if b > a]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for a, b in gaps[:n]:
        out.append([host_label(trace.host, (a + b) / 2), (b - a) / 1e9])
    return out


def host_label(host: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for e in host:
        if e.start_ns <= t <= e.end_ns and (best is None
                                             or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "<no host span>"
