"""From a profiler trace of the measured window to the numbers that
per-layer metrics read.

The reduction reads two kinds of name, both seen on a v5e trace (PERF.md,
section 3):

- on each `/device:TPU:<n>` plane, the line `XLA Modules`, whose events
  are the jitted programs by name and fingerprint (`jit_pic_run_chunk(
  3856464242902868826)`), and the line `XLA Ops`, whose events are their
  operations by HLO text (`%byte_shuffle_block.1 = u8[4,262144]...
  custom-call(...)` for the Pallas shuffle);
- on the `/host:CPU` plane, the benchmark's own spans (`bench.*`, made
  by `jax.profiler.TraceAnnotation` around the calls it makes).

Both share the trace's clock, but not to the microsecond: on a v5e the
first module of a window has shown up to start just before the window's
span. The profiler runs only around the window, so every event in the
trace that overlaps the window's span is the window's, and counts whole;
busy time is clipped to the span. A metric whose source is not in the
trace raises `MissingSource`, which names what was looked for.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import pathlib

WINDOW = "bench.window"
PIC_MODULE = "jit_pic_run_chunk"
SHUFFLE_KERNEL = "%byte_shuffle_block"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
TOP = 10


class MissingSource(LookupError):
    """A declared metric's source is not in the trace."""


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader is given."""
    view: "View"
    counters: dict
    cfg: dict
    traffic: dict
    device_kind: str


class Capture:
    """The profiler over the measured window, host spans and device lines,
    with the Python tracer off."""

    def __init__(self, path, *, host_level: int = 2):
        self.path = pathlib.Path(path)
        self.host_level = host_level

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = self.host_level
        jax.profiler.start_trace(str(self.path), profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def _union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _short(op: str) -> str:
    """`%fusion.14 = f32[...] fusion(...)` -> `%fusion.14`."""
    return op.split(" = ", 1)[0]


class View:
    """Events of one traced window, times in seconds on the trace's clock.

    `devices`: per device, {"modules": [(name, start, end)], "ops": [...]};
    `spans`: host events [(name, start, end)], the benchmark's spans among
    them. Module names keep their fingerprint; `module_time` matches on
    the name before it."""

    def __init__(self, devices: list, spans: list):
        windows = [(s, e) for n, s, e in spans if n == WINDOW]
        if len(windows) != 1:
            raise MissingSource(f"{len(windows)} spans {WINDOW!r} on the "
                                f"host plane, not one")
        self.t0, self.t1 = windows[0]
        self.devices = devices
        self.spans = spans
        if not any(d["modules"] for d in devices):
            raise MissingSource(f"no event on the device lines "
                                f"{MODULES_LINE!r}")

    @classmethod
    def load(cls, path, *, n_devices: int) -> "View":
        """The trace that `Capture` wrote under `path`, or an .xplane.pb
        file."""
        from jax.profiler import ProfileData
        files = ([str(path)] if str(path).endswith(".xplane.pb") else
                 sorted(glob.glob(f"{path}/plugins/profile/*/*.xplane.pb")))
        if not files:
            raise MissingSource(f"no .xplane.pb under {path}")
        return cls.from_profile(ProfileData.from_file(files[-1]),
                                n_devices=n_devices)

    @classmethod
    def from_profile(cls, profile, *, n_devices: int) -> "View":
        devices = {}
        spans = []
        for plane in profile.planes:
            if plane.name.startswith(DEVICE_PLANE):
                dev = {"modules": [], "ops": []}
                for line in plane.lines:
                    key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(
                        line.name)
                    if key:
                        dev[key] = [(e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9)
                                    for e in line.events]
                devices[int(plane.name[len(DEVICE_PLANE):])] = dev
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    spans.extend((e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                                 for e in line.events)
        missing = [i for i in range(n_devices) if i not in devices]
        if missing:
            raise MissingSource(f"no plane {DEVICE_PLANE}<n> for devices "
                                f"{missing}")
        return cls([devices[i] for i in range(n_devices)], spans)

    # ------------------------------------------------------------ helpers
    def _inside(self, events):
        """The events that overlap the window, whole."""
        return [(n, s, e) for n, s, e in events
                if e > self.t0 and s < self.t1]

    def _clipped(self, events):
        return [(max(s, self.t0), min(e, self.t1)) for _, s, e in events
                if e > self.t0 and s < self.t1]

    # ------------------------------------------------------------ numbers
    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        """Seconds in which a program ran on the device, averaged over the
        devices: the union of their modules within the window."""
        return sum(_length(_union(self._clipped(d["modules"])))
                   for d in self.devices) / len(self.devices)

    def module_time(self, name: str) -> float:
        """Device seconds of the window's modules of jitted function
        `name`, summed over the devices."""
        found = [e - s for d in self.devices
                 for n, s, e in self._inside(d["modules"])
                 if n.split("(", 1)[0] == name]
        if not found:
            raise MissingSource(f"no {name!r} module on the device line "
                                f"{MODULES_LINE!r} in the window")
        return sum(found)

    def busy_outside(self, name: str) -> float:
        """Device busy seconds outside the modules of `name`, averaged
        over the devices; `name` itself has to be there."""
        self.module_time(name)
        return sum(_length(_union(self._clipped(
            [m for m in d["modules"] if m[0].split("(", 1)[0] != name])))
            for d in self.devices) / len(self.devices)

    def op_time(self, prefix: str) -> float:
        """Device seconds of the window's operations whose HLO text starts
        with `prefix`, summed over the devices."""
        found = [e - s for d in self.devices
                 for n, s, e in self._inside(d["ops"]) if n.startswith(prefix)]
        if not found:
            raise MissingSource(f"no operation {prefix!r}... on the device "
                                f"line {OPS_LINE!r} in the window")
        return sum(found)

    def span_mean(self, name: str) -> float:
        """Mean seconds of the window's host spans `name`."""
        found = [e - s for n, s, e in self._inside(self.spans) if n == name]
        if not found:
            raise MissingSource(f"no host span {name!r} on the plane "
                                f"{HOST_PLANE!r} in the window")
        return sum(found) / len(found)

    def breakdown(self) -> dict:
        """The device operations that took most time (by module and HLO
        name), and the longest idle gaps of device 0, each named by the
        innermost host event around its middle."""
        d = self.devices[0]
        mods = sorted((s, n.split("(", 1)[0]) for n, s, _ in d["modules"])
        starts = [s for s, _ in mods]
        ops = collections.Counter()
        for n, s, e in self._inside(d["ops"]):
            i = bisect.bisect_right(starts, s) - 1
            ops[f"{mods[i][1] if i >= 0 else '?'} {_short(n)}"] += e - s
        gaps = []
        busy = _union(self._clipped(d["modules"]))
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) / 2))
        gaps.sort(reverse=True)
        host = [(n, s, e) for n, s, e in self.spans if n != WINDOW]
        idle = []
        for length, mid in gaps[:TOP]:
            around = [(e - s, n) for n, s, e in host if s <= mid <= e]
            idle.append([min(around)[1] if around else "host idle", length])
        return {"device_ops": [[k, v] for k, v in ops.most_common(TOP)],
                "idle_gaps": idle}
