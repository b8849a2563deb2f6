"""Read a ``jax.profiler`` trace into device operations and host spans.

On a TPU every chip has a plane ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation run, named by the operation's
HLO text (``%psum.3 = f32[] all-reduce(...), ...``), and ``XLA Modules``
one per program run.  An operation is classified by the opcode in that
text, never by its instruction name: ``lax.psum`` lowers to an
``all-reduce`` named ``psum.N``.  On the CPU (used by the tests) there is
no device plane: the operations are the host events that carry an
``hlo_op`` stat, all counted as device 0, and their names hold no
opcode.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, named ``chipbench.<what>``.
Every time is in nanoseconds on the trace's one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)")
# ops whose events span the ops they run (a TPU trace nests a while
# loop's body inside the loop's own event)
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float
    stats: tuple = ()
    code: str = ""          # the HLO opcode; see :func:`hlo_opcode`

    def __post_init__(self):
        if not self.code:
            object.__setattr__(self, "code", hlo_opcode(self.name))

    @property
    def end(self) -> float:
        return self.start + self.dur

    def stat(self, key, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


@dataclasses.dataclass
class Trace:
    ops: dict          # device index -> [Event] (operations)
    modules: dict      # device index -> [Event] (program runs)
    spans: list        # [Event] host spans named chipbench.*

    def span(self, name: str):
        """The first host span called ``chipbench.<name>``, or None."""
        return next((s for s in self.spans
                     if s.name == f"chipbench.{name}"), None)

    def window(self):
        """(start, end) of the measured window's host span; without one,
        the span of the operations."""
        w = self.span("window")
        if w is not None:
            return w.start, w.end
        evs = [e for evs in self.ops.values() for e in evs]
        if not evs:
            return None
        return min(e.start for e in evs), max(e.end for e in evs)


def op_name(name: str) -> str:
    """``fusion.12`` from a TPU op event's HLO text
    ``%fusion.12 = (...) fusion(...), ...``; other names unchanged."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """``fusion`` from ``fusion.12``."""
    return re.sub(r"\.\d+$", "", name)


_OPCODE = re.compile(r"([a-z][a-z0-9_-]*)\(")


def hlo_opcode(text: str) -> str:
    """The opcode of an operation from its HLO text: ``all-reduce`` from
    ``%psum.3 = f32[] all-reduce(f32[] %x), ...``.  The result shape may
    be a tuple or carry a layout with parentheses, so it is skipped as a
    balanced group or up to the first space.  Text without `` = `` (a
    bare instruction name) gives the name without its number."""
    if " = " not in text:
        return opcode(op_name(text))
    rest = text.split(" = ", 1)[1].lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = _OPCODE.match(rest.lstrip())
    return m.group(1) if m else opcode(op_name(text))


def _events(line):
    for e in line.events:
        yield Event(op_name(e.name), float(e.start_ns), float(e.duration_ns),
                    code=hlo_opcode(e.name))


_DEVICE = re.compile(r"^/device:(?!CPU)[A-Z]+:(\d+)$")


def _is_device(plane) -> bool:
    return _DEVICE.match(plane.name) is not None


def from_xspace(pd) -> Trace:
    """Build a :class:`Trace` from a ``jax.profiler.ProfileData``."""
    planes = list(pd.planes)
    on_device = any(_is_device(p) for p in planes)
    ops, modules, spans, cpu_ops = {}, {}, [], []
    for plane in planes:
        if _is_device(plane):
            d = int(_DEVICE.match(plane.name).group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(d, []).extend(_events(line))
                elif line.name == "XLA Modules":
                    modules.setdefault(d, []).extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        spans.append(Event(e.name, float(e.start_ns),
                                           float(e.duration_ns)))
                    elif not on_device and not e.name.startswith("end:"):
                        ev = Event(e.name, float(e.start_ns),
                                   float(e.duration_ns), tuple(e.stats))
                        if ev.stat("hlo_op") is not None:
                            cpu_ops.append(ev)
    if not on_device and cpu_ops:
        ops = {0: cpu_ops}
    for evs in list(ops.values()) + list(modules.values()):
        evs.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_xspace(ProfileData.from_file(max(files,
                                                 key=os.path.getmtime)))


# -- interval arithmetic ------------------------------------------------------

def clip(events, lo: float, hi: float) -> list:
    """(start, end) of each event cut to [lo, hi]; empty ones dropped."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def length(intervals) -> float:
    return sum(t - s for s, t in intervals)


def subtract(a, b) -> list:
    """Parts of disjoint sorted intervals ``a`` not covered by ``b``."""
    out, b = [], union(b)
    j = 0
    for s, t in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < t:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < t:
            out.append((cur, t))
    return out


def is_collective(code: str) -> bool:
    """Whether an HLO opcode is a collective (its async start and done
    included)."""
    return bool(COLLECTIVE.match(code))


def is_container(code: str) -> bool:
    return code in CONTAINERS


def gaps(busy, lo: float, hi: float) -> list:
    """Idle (start, end) intervals of ``busy`` inside [lo, hi]."""
    return subtract([(lo, hi)], busy)
