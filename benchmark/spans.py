"""The program's own spans, read by the benchmark: the node keeps a ring
of completed flat span records (`GET /_telemetry/spans`: trace_id,
span_id, parent_id, name, start_ns, end_ns on `time.monotonic_ns()`, the
clock of `run.window`, `run.trace_slice` and the load generator's
samples), and this module fetches it once after the window, indexes it
by request, computes self times, and joins the host spans with the
device ops of the traced slice.

**The join.** A device plane's timestamps are not on the host's clock
(a TPU plane counts nanoseconds from the profiler session's start, which
the reduction does not keep), and with the profiler's host tracer off no
annotation reaches the trace. So the offset `device - host` is bracketed
by causality. The unit is the wave (a B=1 request is one wave): a
plane's device ops are cut into program runs (by the plane's "XLA
Modules" events, one a run and named for the jitted function, so two
programs that queue back to back are still two runs; where a plane has
no such line, where the device stood still for more than `RUN_GAP_NS`),
the k-th `dispatch` span of the slice owns the next runs, as
many as its `programs` (and its `device_wait`'s, for the row-concat
program) say, and for every wave

    first op start >= dispatch.start + offset
    last op end    <= device_wait.end + offset

which bounds the offset from both sides. The width of the feasible
interval is the error of every attribution made with it; an empty one
means the matching is wrong, and the neighbouring shifts are tried
before giving up (None). A feasible interval does not prove the
matching: `runs_agree` checks it against what the bracket did not use,
the time each executable's runs take.

**Every plane.** The join runs on each chip's plane (`join_planes`):
on an SPMD mesh every plane runs every program, on one chip there is
one plane. The planes of one trace count from one zero, so the offset's
feasible interval is the intersection of the planes' intervals; an
empty intersection is no join. With the offset, a plane's recorded
interval (first op to last, `trace_reduce`) lies on the host's clock:
that is the interval the requests are counted over and idle is
attributed in (`recorded_intervals`), not the host's slice around the
profiler's start and stop, which is longer at both ends.

Against a node that has no such endpoint (a parent commit of the PR that
added it), and without a device plane, every function here returns None
and nothing raises; anything else that goes wrong is a bug and fails
the traced run.
"""

from __future__ import annotations

import bisect
import copy
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from benchmark.trace_reduce import union

SERVED_ROUTES = ("_search", "_msearch")
# where one program ends and the next begins. The ops of one program
# follow one another within nanoseconds, with a stall of 1.0-1.3 us
# once or twice a run (v5e traces of the dense kernel, PR 25); the next
# program of a B=1 closed loop starts 12 ms later at the least.
RUN_GAP_NS = 10_000
# runs of one executable take the same time to within this share of
# their median (`runs_agree`); the two QB buckets of the dense kernel
# lie a factor of two apart
RUNS_AGREE_WITHIN = 0.25
INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


# ------------------------------------------------------------- the ring

class Spans:
    """The fetched ring, indexed."""

    def __init__(self, body: dict):
        self.dropped = int(body.get("dropped", 0))
        self.anchor = body.get("anchor", {})
        self.spans: List[dict] = body["spans"]
        self.by_trace: Dict[int, List[dict]] = {}
        self.children: Dict[int, List[dict]] = {}
        for s in self.spans:
            self.by_trace.setdefault(s["trace_id"], []).append(s)
            self.children.setdefault(s["parent_id"], []).append(s)

    def self_ns(self, span: dict) -> int:
        """A span's duration less what its children cover."""
        lo, hi = span["start_ns"], span["end_ns"]
        covered = union([(max(c["start_ns"], lo), min(c["end_ns"], hi))
                         for c in self.children.get(span["span_id"], [])
                         if c["end_ns"] > lo and c["start_ns"] < hi])
        return (hi - lo) - sum(b - a for a, b in covered)

    def requests(self, t0_ns: int, t1_ns: int) -> List[dict]:
        """The served requests (`http.request` of a search route) that
        lie inside [t0, t1], by start."""
        out = [s for s in self.spans
               if s["name"] == "http.request"
               and s.get("attributes", {}).get("route") in SERVED_ROUTES
               and s["start_ns"] >= t0_ns and s["end_ns"] <= t1_ns]
        return sorted(out, key=lambda s: s["start_ns"])

    def named(self, trace_id: int, name: str) -> List[dict]:
        return [s for s in self.by_trace.get(trace_id, [])
                if s["name"] == name]


def fetch(run) -> Optional[Spans]:
    """The node's span ring over the window, once a run; None where the
    node has no span ring."""
    if "_spans" not in run.__dict__:
        t0, _ = run.window
        path = f"/_telemetry/spans?since_ns={int((t0 - 1.0) * 1e9)}"
        try:
            run._spans = Spans(run.call("GET", path))
        except (RuntimeError, KeyError, ValueError):
            run._spans = None
    return run._spans


def window_ns(run) -> Tuple[int, int]:
    """The measured window on the spans' clock: its start to the last
    counted answer."""
    t0, t1 = run.window
    return int(t0 * 1e9), int(max(t1, getattr(run, "drained", t1)) * 1e9)


# ------------------------------------------------------------ the waves

class Wave:
    """One `dispatch` span with the `device_wait` of the same wave of
    the same envelope, and the device ops the join gave it."""

    def __init__(self, dispatch: dict, wait: dict):
        self.trace_id = dispatch["trace_id"]
        self.dispatch = dispatch
        self.wait = wait
        attrs = dispatch.get("attributes", {})
        self.programs = int(attrs.get("programs", 1)) \
            + int(wait.get("attributes", {}).get("programs", 0))
        self.fingerprints = attrs.get("fingerprints") \
            or ([attrs["fingerprint"]] if "fingerprint" in attrs else [])
        self.start_ns = dispatch["start_ns"]
        self.end_ns = wait["end_ns"]
        self.runs: List["Run"] = []

    @property
    def trace_ids(self) -> List[int]:
        """The requests this wave serves: its own, or the ones a
        scheduler-coalesced wave lists."""
        return self.dispatch.get("attributes", {}).get("trace_ids") \
            or [self.trace_id]


def waves_of(spans: Spans) -> List[Wave]:
    """Every completed wave of the ring, by dispatch start."""
    waits = {}
    for s in spans.spans:
        if s["name"] == "device_wait":
            waits[(s["parent_id"],
                   s.get("attributes", {}).get("wave", 0))] = s
    out = []
    for s in spans.spans:
        if s["name"] != "dispatch":
            continue
        wait = waits.get((s["parent_id"],
                          s.get("attributes", {}).get("wave", 0)))
        if wait is not None:
            out.append(Wave(s, wait))
    return sorted(out, key=lambda w: w.start_ns)


# ------------------------------------------------------- the device ops

class Run:
    """One program run: the device ops (by start) inside one module
    event, with the module's name; or, cut at the gap, consecutive
    device ops with no gap between them, and no name."""

    __slots__ = ("events", "start", "end", "name")

    def __init__(self, events, name: Optional[str] = None):
        self.events = events
        self.start = events[0][1]
        self.end = max(e[2] for e in events)
        self.name = name


def _cut_at_gaps(events) -> List[Run]:
    runs, cur, cur_end = [], [], 0
    for ev in events:
        if cur and ev[1] - cur_end > RUN_GAP_NS:
            runs.append(Run(cur))
            cur = []
        cur_end = max(cur_end, ev[2]) if cur else ev[2]
        cur.append(ev)
    if cur:
        runs.append(Run(cur))
    return runs


def program_runs(events, modules=()) -> List[Run]:
    """A plane's device ops (name, start, end) as program runs, by
    start. Where the plane has module events (`modules`, the "XLA
    Modules" line: one event a program run), a run is the ops that
    start inside one of them and carries its name; ops inside none (the
    profiler cut their module event away at an edge of the trace) are
    cut like a plane without the line: where the device stood still for
    more than `RUN_GAP_NS`. Which of the two is decided by what the
    trace holds."""
    events = sorted(events, key=lambda e: e[1])
    if not modules:
        return _cut_at_gaps(events)
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    inside: Dict[int, list] = {}
    outside = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < max(modules[i][2], modules[i][1] + 1):
            inside.setdefault(i, []).append(ev)
        else:
            outside.append(ev)
    runs = [Run(evs, modules[i][0]) for i, evs in inside.items()]
    return sorted(runs + _cut_at_gaps(outside), key=lambda r: r.start)


class Join:
    """One plane's waves with their runs, and the offset that puts
    device time on the host's clock: host = device - offset, offset in
    [lo, hi]. `offset` is the middle of the plane's own interval until
    `MeshJoin` sets the planes' common one."""

    def __init__(self, waves, runs, lo: int, hi: int, matched_runs: int,
                 all_waves=()):
        self.waves = waves          # those that were given runs (copies)
        self.all_waves = all_waves  # every completed wave of the ring
        self.runs = runs            # every run of the plane
        self.lo, self.hi = lo, hi
        self.offset = (lo + hi) // 2
        self.matched_runs = matched_runs

    @property
    def bracket_ns(self) -> int:
        return self.hi - self.lo

    @property
    def recorded_ns(self) -> Tuple[int, int]:
        """The plane's recorded interval, first op to last, on the
        host's clock."""
        return (min(r.start for r in self.runs) - self.offset,
                max(r.end for r in self.runs) - self.offset)


class MeshJoin:
    """The planes' joins under one offset: the middle of the
    intersection of their feasible intervals."""

    def __init__(self, planes: Dict[str, Join]):
        self.planes = planes
        self.lo = max(jn.lo for jn in planes.values())
        self.hi = min(jn.hi for jn in planes.values())
        self.offset = (self.lo + self.hi) // 2
        for jn in planes.values():
            jn.offset = self.offset

    @property
    def bracket_ns(self) -> int:
        return self.hi - self.lo


def _assign(waves: List[Wave], runs: List[Run], first_wave: int,
            first_run: int):
    """Runs [first_run:] to waves [first_wave:] in order, each wave its
    `programs`; the bounds on the offset that the fully served waves
    give. None when nothing matches."""
    lo, hi = None, None
    r = first_run
    given = []
    for w in waves[first_wave:]:
        take = runs[r:r + w.programs]
        if len(take) < w.programs:
            break
        r += w.programs
        given.append((w, take))
        upper = take[0].start - w.start_ns      # offset <= this
        lower = take[-1].end - w.end_ns         # offset >= this
        hi = upper if hi is None else min(hi, upper)
        lo = lower if lo is None else max(lo, lower)
    if not given:
        return None
    return lo, hi, given, r - first_run


def join(waves: List[Wave], events, slice_ns: Tuple[int, int],
         max_shift: int = 3, modules=()) -> Optional[Join]:
    """Match the waves that can have run inside the traced slice with
    the program runs of one device plane (`events`, its ops; `modules`,
    its module events where it has them). The first run of the trace
    may belong to a wave already in flight when the profiler started
    (the run is then cut short, or whole), so the first few waves and
    the first few runs are each tried as the start; the feasible
    matching that places most runs wins. The waves of the result are
    copies: another plane's join gives the same waves other runs."""
    runs = program_runs(events, modules)
    a, b = slice_ns
    # a wave can have device ops in the trace if it was open at any time
    # from a little before the slice (the profiler starts recording
    # before `start_trace` returns) to its end
    margin = 2_000_000_000
    live = [w for w in waves if w.end_ns >= a - margin and w.start_ns <= b]
    if not live or not runs:
        return None
    best = None
    for first_wave in range(min(max_shift + 1, len(live))):
        for first_run in range(min(max_shift + 1, len(runs))):
            got = _assign(live, runs, first_wave, first_run)
            if got is None:
                continue
            lo, hi, given, n = got
            if lo > hi:
                continue
            # device zero is the profiler session's start: not after the
            # slice began, and not long before it; and no op is recorded
            # after the profiler was stopped
            zero_host = -(lo + hi) // 2
            if not (a - 30_000_000_000 <= zero_host <= a + 1_000_000):
                continue
            if runs[-1].end + zero_host > b + (hi - lo) + 1_000_000:
                continue
            key = (n, -(hi - lo))
            if best is None or key > best[0]:
                best = (key, lo, hi, given, n)
    if best is None:
        return None
    _, lo, hi, given, n = best
    matched = []
    for w, take in given:
        w = copy.copy(w)
        w.runs = take
        matched.append(w)
    return Join(matched, runs, lo, hi, n, all_waves=waves)


def join_planes(waves: List[Wave], planes: Dict[str, list],
                slice_ns: Tuple[int, int],
                modules: Optional[Dict[str, list]] = None
                ) -> Optional[MeshJoin]:
    """`join` on every plane that recorded an op, under one offset: the
    planes of a trace count from one zero, so the offset lies in every
    plane's feasible interval. None where a plane has no feasible join,
    or the intersection of the planes' intervals is empty."""
    joins = {}
    for name, events in planes.items():
        if not events:
            continue
        jn = join(waves, events, slice_ns,
                  modules=(modules or {}).get(name, ()))
        if jn is None:
            return None
        joins[name] = jn
    if not joins:
        return None
    mesh = MeshJoin(joins)
    return mesh if mesh.lo <= mesh.hi else None


def device_join(run) -> Optional[MeshJoin]:
    """The join of the run's spans with the device planes of its traced
    slice, once a run. Without one the traced window and the count of
    its requests keep the host's slice, and stderr says so."""
    if "_join" not in run.__dict__:
        run._join = None
        spans = fetch(run)
        trace = getattr(run, "trace", None)
        if trace is None or not trace.planes or run.trace_slice is None:
            return None
        a, b = run.trace_slice
        # nothing is recorded of a wave that was over before the
        # profiler was started
        called = getattr(run, "trace_called", None)
        since = 0 if called is None else int(called * 1e9)
        mesh = None if spans is None else join_planes(
            [w for w in waves_of(spans) if w.end_ns >= since],
            trace.planes, (int(a * 1e9), int(b * 1e9)), trace.modules)
        run._join = mesh
        if mesh is None:
            trace.keep_host_window()
            sys.stderr.write(
                "[spans] no feasible join of waves and device ops: the "
                "traced window and its requests are the host's slice, "
                f"{b - a:.4f}s\n")
            return None
        for name, jn in mesh.planes.items():
            lo, hi = jn.recorded_ns
            named = sum(1 for r in jn.runs if r.name is not None)
            sys.stderr.write(
                f"[spans] {len(jn.waves)} waves own {jn.matched_runs} of "
                f"{len(jn.runs)} program runs ({named} by a module event)"
                f" on {name}; offset {mesh.offset} ns +- "
                f"{mesh.bracket_ns // 2}; first op {lo / 1e9 - a:+.4f}s "
                f"from the slice's start, last {hi / 1e9 - b:+.4f}s from "
                f"its end\n")
    return run._join


def recorded_intervals(run) -> Optional[List[Tuple[float, float]]]:
    """Every joined plane's recorded interval on the host's clock,
    seconds (to half the bracket, `span_clock_bracket_us`): what the
    traced device time belongs to. None without a join."""
    mesh = device_join(run)
    if mesh is None:
        return None
    return [(lo / 1e9, hi / 1e9) for lo, hi in
            (jn.recorded_ns for jn in mesh.planes.values())]


# -------------------------------------------------------- idle, by span

IDLE_PARTS = ("between_requests", "before_first_op", "inside_request",
              "after_last_op")


def idle_parts(spans: Spans, jn: Join, slice_ns: Tuple[int, int]
               ) -> Dict[str, int]:
    """One plane's idle time inside `slice_ns` (host clock; its
    recorded interval in a run, `mesh_idle_parts`), each
    instant put down to what the host was in: no served request open;
    a request open whose first op has not started (decode, parse, pack,
    upload); between the ops of an open request (a later program
    waiting for the host); after an open request's last op (readback,
    render, write). Where several requests are open the one furthest
    along decides: inside, then before, then after. Nanoseconds."""
    a, b = slice_ns
    off = jn.offset
    busy = union([(max(ev[1] - off, a), min(ev[2] - off, b))
                  for r in jn.runs for ev in r.events
                  if ev[2] - off > a and ev[1] - off < b])
    idle, t = [], a
    for lo, hi in busy:
        if lo > t:
            idle.append((t, lo))
        t = max(t, hi)
    if t < b:
        idle.append((t, b))
    # each served request: open [s, e], first op f, last op l (host)
    first: Dict[int, int] = {}
    last: Dict[int, int] = {}
    for w in jn.waves:
        if not w.runs:
            continue
        f, l = w.runs[0].start - off, w.runs[-1].end - off
        for tid in w.trace_ids:
            first[tid] = min(first.get(tid, f), f)
            last[tid] = max(last.get(tid, l), l)
    reqs = []
    for s in spans.spans:
        if s["name"] == "http.request" and s.get("attributes", {}).get(
                "route") in SERVED_ROUTES and s["end_ns"] > a \
                and s["start_ns"] < b:
            tid = s["trace_id"]
            reqs.append((s["start_ns"], s["end_ns"],
                         first.get(tid), last.get(tid)))
    reqs.sort()
    starts = [r[0] for r in reqs]
    longest = max((r[1] - r[0] for r in reqs), default=0)
    out = dict.fromkeys(IDLE_PARTS, 0)
    for g0, g1 in idle:
        # requests that can overlap the gap
        i0 = bisect.bisect_left(starts, g0 - longest)
        i1 = bisect.bisect_right(starts, g1)
        near = [r for r in reqs[i0:i1] if r[1] > g0]
        cuts = {g0, g1}
        for s, e, f, l in near:
            for t in (s, e, f, l):
                if t is not None and g0 < t < g1:
                    cuts.add(t)
        cuts = sorted(cuts)
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) // 2
            phase = None
            for s, e, f, l in near:
                if not s <= mid < e:
                    continue
                if f is None or mid < f:
                    p = 1
                elif mid <= l:
                    p = 2
                else:
                    p = 0
                phase = p if phase is None else max(phase, p)
            name = "between_requests" if phase is None else (
                "after_last_op", "before_first_op", "inside_request")[phase]
            out[name] += hi - lo
    return out


def mesh_idle_parts(spans: Spans, mesh: MeshJoin) -> Dict[str, float]:
    """`idle_parts` of every plane inside its own recorded interval,
    averaged over the planes: the parts sum to the mean window less the
    mean busy time, as `trace_reduce.Reduction` reads them."""
    out = dict.fromkeys(IDLE_PARTS, 0.0)
    for jn in mesh.planes.values():
        for part, ns in idle_parts(spans, jn, jn.recorded_ns).items():
            out[part] += ns / len(mesh.planes)
    return out


def mesh_runs_agree(mesh: MeshJoin) -> Optional[float]:
    """`runs_agree` of the plane that agrees least; None where no plane
    has anything to compare."""
    shares = [runs_agree(jn) for jn in mesh.planes.values()]
    return min((s for s in shares if s is not None), default=None)


def runs_agree(jn: Join) -> Optional[float]:
    """The join checked against what the bracket did not use: a run of
    the executable its wave's `dispatch` names takes the time that
    executable's other runs take. Share of device time, over the runs
    the join gave to a wave, of those within `RUNS_AGREE_WITHIN` of the
    median of their executable's runs. A matching shifted by one wave
    pairs runs with the neighbour's executable and reads well under 1
    wherever the slice holds executables of different cost. Left out:
    the plane's first and last run (the profiler cuts them short), the
    row-concat program, and an executable with one run; None when that
    leaves nothing to compare."""
    edge = {id(jn.runs[0]), id(jn.runs[-1])}
    took: Dict[str, List[int]] = {}
    for w in jn.waves:
        for r, fp in zip(w.runs, w.fingerprints):
            if id(r) not in edge:
                took.setdefault(fp, []).append(r.end - r.start)
    total = agree = 0
    for durations in took.values():
        if len(durations) < 2:
            continue
        median = statistics.median(durations)
        for d in durations:
            total += d
            if abs(d - median) <= RUNS_AGREE_WITHIN * median:
                agree += d
    return agree / total if total else None


# ------------------------------------------------------ stage, by scope

def instruction(event_name: str) -> str:
    """The HLO instruction's name from an event's (`%fusion.5 =
    f32[...] fusion(...)` on a TPU, the bare name elsewhere)."""
    m = INSTRUCTION.match(event_name.strip())
    return m.group(1) if m else event_name.strip()


def scope_maps(run) -> Optional[Dict[str, Dict[str, str]]]:
    """{executable fingerprint -> {instruction -> stage}} from the
    node's executable census; None where the node has no such map. An
    executable whose map the node refuses (`_error`: loaded from the
    compile cache, compiled from another stage layout) has none here."""
    if "_scopes" not in run.__dict__:
        run._scopes = None
        try:
            body = run.call("GET", "/_telemetry/kernels?scopes=true")
            execs = body["kernels"]["census"]["executables"]
        except (RuntimeError, KeyError, ValueError):
            return None
        maps = {}
        for e in execs:
            scopes = e.get("scopes")
            if not isinstance(scopes, dict):
                continue
            if "_error" in scopes:
                sys.stderr.write(f"[spans] no scope map for "
                                 f"{e.get('family')} {e['fingerprint']}: "
                                 f"{scopes['_error']}\n")
            else:
                maps[e["fingerprint"]] = scopes
        run._scopes = maps or None
    return run._scopes


def stage_ns(jn: Join, maps: Dict[str, Dict[str, str]]
             ) -> Optional[Dict[Optional[str], int]]:
    """Device-op nanoseconds of every run of one plane by stage as the
    census writes it (`stage`; `~stage` where it was inferred from the
    op's neighbours; None: of no stage). An op of a wave's run is read
    in the map of the executable the wave dispatched for that run; an
    op of a run no wave was given (cut at an edge of the trace) in the
    maps of the slice's executables, where they agree. None where an
    executable dispatched in the slice has no map: a stage metric is
    then left out, not read from a part of the slice."""
    fps = {fp for w in jn.waves for fp in w.fingerprints}
    if not fps <= set(maps):
        return None
    merged: Dict[str, Optional[str]] = {}
    for fp in fps:
        for instr, st in maps[fp].items():
            merged[instr] = st if merged.get(instr, st) == st else None
    owner = {}
    for w in jn.waves:
        for i, r in enumerate(w.runs):
            # the runs past the dispatched executables are the
            # device_wait's own program (concat_rows): no stage
            owner[id(r)] = maps[w.fingerprints[i]] \
                if i < len(w.fingerprints) else {}
    out: Dict[Optional[str], int] = {}
    for r in jn.runs:
        m = owner.get(id(r), merged)
        for name, lo, hi in r.events:
            st = m.get(instruction(name))
            out[st] = out.get(st, 0) + (hi - lo)
    return out


def mesh_stage_ns(mesh: MeshJoin, maps: Dict[str, Dict[str, str]]
                  ) -> Optional[Dict[Optional[str], float]]:
    """`stage_ns` of every plane, averaged over the planes (a chip's
    share of a query's device time, as `device_ms_per_query` has it);
    None where any plane ran an executable without a map."""
    out: Dict[Optional[str], float] = {}
    for jn in mesh.planes.values():
        by_stage = stage_ns(jn, maps)
        if by_stage is None:
            return None
        for st, ns in by_stage.items():
            out[st] = out.get(st, 0.0) + ns / len(mesh.planes)
    total = sum(out.values()) or 1
    inferred = sum(ns for st, ns in out.items() if st and st[0] == "~")
    sys.stderr.write(
        f"[spans] stages: {100 * inferred / total:.2f}% of device-op time "
        f"in ops whose stage is inferred from their neighbours (~), "
        f"{100 * out.get(None, 0) / total:.2f}% in ops of no stage\n")
    return out
