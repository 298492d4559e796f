"""The program's own spans and named device programs, read from the run's
``jax.profiler`` trace: what ``traceread`` (the harness's ``bench.*`` spans
and the device's busy time) does not look at.

The program writes its spans into the profiler's trace itself
(``nnstreamer_tpu/obs/spans.py``): ``nns.<layer>.<what>`` annotations for
work a thread does, end-stamped markers carrying ``dur_ns`` for waits
measured after the fact, each with ``trace`` / ``span`` / ``parent`` (and
``element``, ``bytes`` ...) as metadata. Its jitted programs carry stable
names (``jit_nns_filter_<model>``, ``jit_nns_llm_<what>``) on the device
planes' ``XLA Modules`` line, and its models' ``jax.named_scope``s reach
each ``XLA Ops`` event as the stat ``tf_op``
(``jit(nns_filter_vit_h14)/.../block/attn/...``). That stat hangs on the
event's *metadata*, which ``ProfileData`` does not hand out, so the
device planes' metadata is read from the file's bytes (``op_scopes``).

Plain form (``load``), traceread's with a fourth element: ``{"planes":
[{"name", "lines": [{"name", "events": [[name, start_ns, duration_ns,
meta], ...]}]}]}``; ``meta`` is a dict of the annotation's metadata for a
host event, ``{"scope": tf_op}`` for a device operation, ``{}`` otherwise.
Kept: host events named ``nns.*`` or ``bench.*``, and the device planes'
``XLA Modules`` and ``XLA Ops`` lines. The arithmetic below runs on that
form, so on the recorded synthetic trace ``selftest/trace_prog_small.json``
too. A program without these spans and names (the parent of the PR that
added them) gives empty lists, and every reader built on this returns
None."""
from __future__ import annotations

import functools
import glob
import os
import sys
import time

from nnsbench.traceread import DEVICE_PLANE, WINDOW_SPAN, union

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROGRAM_PREFIX = "nns."
HARNESS_PREFIX = "bench."
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
SCOPE_STAT = "tf_op"
UNATTRIBUTED = "unattributed"
MIN_SPANS = 5         # a reader that finds fewer says nothing


# -- the xplane file's bytes: event metadata of the device planes ---------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, wire type, value) of one protobuf message;
    length-delimited values come as ``(lo, hi)`` into ``buf``, so a
    megabyte of host events is stepped over, not read."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def _map_entry(buf, span):
    key, value = 0, (0, 0)
    for num, _, val in _fields(buf, *span):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def op_scopes(path: str) -> dict:
    """{device operation's name as the trace gives it: its scope} from
    the ``tf_op`` stat of the device planes' event metadata (XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for num, wire, plane in _fields(buf, 0, len(buf)):
        if num != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for pnum, _, val in _fields(buf, *plane):
            if pnum == 2:
                name = buf[val[0]:val[1]].decode()
            elif pnum == 4:
                events.append(val)
            elif pnum == 5:
                key, meta = _map_entry(buf, val)
                for snum, _, sval in _fields(buf, *meta):
                    if snum == 2:
                        stat_names[key] = buf[sval[0]:sval[1]].decode()
        if not DEVICE_PLANE.match(name):
            continue
        for entry in events:
            _, meta = _map_entry(buf, entry)
            ev_name, scope = "", None
            for mnum, _, val in _fields(buf, *meta):
                if mnum == 2:
                    ev_name = buf[val[0]:val[1]].decode()
                elif mnum == 5:
                    stat = {n: v for n, _, v in _fields(buf, *val)}
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = buf[stat[5][0]:stat[5][1]].decode()
                    elif 7 in stat:
                        scope = stat_names.get(stat[7])
            if scope:
                out[ev_name] = scope
    return out


# -- trace -> plain form ---------------------------------------------------

def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    try:
        scopes = op_scopes(path)
    except (ValueError, IndexError, UnicodeDecodeError) as exc:
        print(f"progtrace: no operation scopes ({exc})", file=sys.stderr)
        scopes = {}
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in (MODULES_LINE, OPS_LINE):
                    continue
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           {"scope": scopes[ev.name]}
                           if ev.name in scopes else {}]
                          for ev in line.events]
            else:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           {k: v for k, v in ev.stats}]
                          for ev in line.events
                          if ev.name.startswith((PROGRAM_PREFIX,
                                                 HARNESS_PREFIX))]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- plain form -> what the readers ask ------------------------------------

class Span:
    """One host span on the profiler's clock. A marker (``wait``) was
    stamped where the wait ended and carried its length."""

    __slots__ = ("name", "lo", "hi", "thread", "meta", "wait")

    def __init__(self, name, start, dur, thread, meta):
        self.name, self.thread, self.meta = name, thread, meta
        self.wait = "dur_ns" in meta
        if self.wait:
            self.lo, self.hi = start - int(float(meta["dur_ns"])), start
        else:
            self.lo, self.hi = start, start + dur


class ProgTrace:
    def __init__(self, trace: dict):
        self.spans = []            # nns.* and bench.*, every host thread
        self.modules = []          # first device: [name, lo, hi]
        self.ops = []              # first device: [name, lo, hi, scope]
        self.window = None
        devices = {}
        for plane in trace["planes"]:
            if DEVICE_PLANE.match(plane["name"]):
                devices[plane["name"]] = {ln["name"]: ln["events"]
                                          for ln in plane["lines"]}
                continue
            for n, line in enumerate(plane["lines"]):
                thread = (plane["name"], n)
                for name, start, dur, meta in line["events"]:
                    if name == WINDOW_SPAN:
                        self.window = (start, start + dur)
                    else:
                        self.spans.append(Span(name, start, dur, thread,
                                               meta))
        if devices:
            first = devices[min(devices)]
            self.modules = [[_module_name(n), s, s + d]
                            for n, s, d, _ in first.get(MODULES_LINE, [])]
            self.ops = [[n, s, s + d, m.get("scope", "")]
                        for n, s, d, m in first.get(OPS_LINE, [])]
        self.has_device = bool(devices)
        if self.window is None:
            edges = [(s.lo, s.hi) for s in self.spans] + \
                [(lo, hi) for _, lo, hi, *_ in self.modules + self.ops]
            self.window = ((min(lo for lo, _ in edges),
                            max(hi for _, hi in edges)) if edges
                           else (0, 0))

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def inside(self, lo, hi) -> bool:
        return self.window[0] <= lo and hi <= self.window[1]

    def regions(self, name):
        """The spans ``name`` that lie wholly inside the traced stretch."""
        return [s for s in self.spans if s.name == name and not s.wait
                and self.inside(s.lo, s.hi)]

    def waits(self, name):
        """The waits ``name`` that ended inside the traced stretch (one
        that began before it keeps its whole length: it is a share of a
        buffer's latency, not of the stretch)."""
        return [s for s in self.spans if s.name == name and s.wait
                and self.inside(s.hi, s.hi)]

    def busy_ns(self, name) -> int:
        """Union of the spans ``name``, clipped to the stretch."""
        lo, hi = self.window
        return sum(b - a for a, b in union(
            [max(s.lo, lo), min(s.hi, hi)] for s in self.spans
            if s.name == name and not s.wait
            and min(s.hi, hi) > max(s.lo, lo)))

    def busy_ms_per(self, name, per):
        """Milliseconds the spans ``name`` cover in the stretch for each
        span ``per`` that lies inside it; None under ``MIN_SPANS`` of
        those."""
        n = len(self.regions(per))
        if n < MIN_SPANS:
            return None
        return self.busy_ns(name) / n / 1e6

    def self_ns(self, span) -> int:
        """A span's time minus what its children cover: the spans (waits
        excepted) that the same thread opened inside it."""
        inner = union([s.lo, s.hi] for s in self.spans
                      if s is not span and s.thread == span.thread
                      and not s.wait and span.lo <= s.lo
                      and s.hi <= span.hi)
        return (span.hi - span.lo) - sum(b - a for a, b in inner)

    def module_ns(self, prefix) -> list:
        """Device durations of the programs whose name starts with
        ``prefix`` and that ran wholly inside the stretch."""
        return [hi - lo for name, lo, hi in self.modules
                if name.startswith(prefix) and self.inside(lo, hi)]

    def scope_share(self, part):
        """Share of the device-operation time inside the stretch whose
        scope holds ``part``; None where no operation carries a scope."""
        lo, hi = self.window
        total = scoped = hit = 0
        for _, a, b, scope in self.ops:
            d = min(b, hi) - max(a, lo)
            if d <= 0:
                continue
            total += d
            if scope:
                scoped += d
            if part in scope:
                hit += d
        if not scoped or not hit:
            return None
        return hit / total

    def idle_gaps(self) -> dict:
        """{who: idle ns} of the first device inside the stretch: each
        gap between its operations goes to the innermost ``nns.*`` span
        (the shortest) among those that cover at least half of it, else
        to the ``bench.*`` span that covers most of it and at least half,
        else to ``unattributed``."""
        lo, hi = self.window
        busy = [[max(a, lo), min(b, hi)] for a, b in union(
            [a, b] for _, a, b, _ in self.ops) if min(b, hi) > max(a, lo)]
        # a sweep: the gaps come in time order, so only the spans open
        # around the current one are looked at
        work = sorted((s for s in self.spans if not s.wait),
                      key=lambda s: s.lo)
        nxt, live = 0, []
        gaps = {}
        edge = lo
        for a, b in busy + [[hi, hi]]:
            if a > edge:
                gap = a - edge
                while nxt < len(work) and work[nxt].lo < a:
                    live.append(work[nxt])
                    nxt += 1
                live = [s for s in live if s.hi > edge]
                cover = {s: min(s.hi, a) - max(s.lo, edge) for s in live}
                cover = {s: o for s, o in cover.items() if o * 2 >= gap}
                ours = [s for s in cover
                        if s.name.startswith(PROGRAM_PREFIX)]
                if ours:
                    who = min(ours, key=lambda s: s.hi - s.lo).name
                elif cover:
                    who = max(cover, key=cover.get).name
                else:
                    who = UNATTRIBUTED
                gaps[who] = gaps.get(who, 0) + gap
            edge = max(edge, b)
        return gaps


def _module_name(name: str) -> str:
    """``jit_nns_filter_vit_h14(3170208683380319506)`` without its
    fingerprint."""
    return name.split("(", 1)[0]


def mean_ms(values_ns):
    """Mean in milliseconds; None under ``MIN_SPANS`` values."""
    values_ns = list(values_ns)
    if len(values_ns) < MIN_SPANS:
        return None
    return sum(values_ns) / len(values_ns) / 1e6


@functools.lru_cache(maxsize=2)
def _of_dir(trace_dir: str):
    t = time.perf_counter()
    try:
        prog = ProgTrace(load(trace_dir))
    except FileNotFoundError as exc:
        print(f"progtrace: {exc}", file=sys.stderr)
        return None
    if not prog.has_device:
        print("progtrace: the trace holds no device plane; the program's "
              "spans are not laid beside anything", file=sys.stderr)
        return None
    print(f"progtrace: {len(prog.spans)} spans, {len(prog.modules)} "
          f"programs, {len(prog.ops)} operations read in "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    return prog


def of_run(run):
    """The run's trace (``.bench_out/trace-<cell>``, where ``run.py``
    puts it and until it deletes it), read once a process; None where
    there is no trace or no device plane in it."""
    if run.get("trace") is None:
        return None
    return _of_dir(os.path.join(ROOT, ".bench_out",
                                "trace-" + run["ctx"].cell["name"]))
