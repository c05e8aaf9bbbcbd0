#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once: build the configuration from the
seed, start the node the way `python -m opensearch_tpu` does, install the
index, warm the traffic's query classes, measure for `--seconds` over the
real socket, judge stored responses against the plain reference, and
print ONE JSON line last.

    python3 benchmark/run.py --workload sift-knn-open --seed 7 \
        --seconds 30 --trace 0

Everything that belongs to one configuration, one traffic mix or one
metric is a file found by the name BENCHMARK.json gives it:

    benchmark/configs/<config>.json   sizes, source, guarantees, `builder`
    benchmark/configs/<builder>.py    generator + plain reference
    benchmark/traffic/<traffic>.json  loop, rate or clients, classes
    benchmark/metrics/<metric>.json   `reader` + its parameters
    benchmark/metrics/readers/<reader>.py

The line's last key, `compared`, and the last lines of standard error
hold every number `correct` rests on beside its limit (oracle.py).

It runs on what jax gives it and refuses anything but a TPU with the
chips the cell asks for; a cell of several chips also has to have run
on a mesh of that width (`mesh_width`, read after the window from what
the node reports). The one exception is `--dry-run` with
JAX_PLATFORMS=cpu set by the caller: tiny sizes from the files' `dry_run`
blocks, for tests and debugging; it says so and reports platform "cpu".
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse            # noqa: E402
import contextlib          # noqa: E402
import functools           # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import random              # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402
import tempfile            # noqa: E402
import threading           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import loadgen   # noqa: E402

MSEARCH_TYPE = "application/x-ndjson"
SEARCH_TYPE = "application/json"


def log(msg: str) -> None:
    sys.stderr.write(f"[bench +{time.monotonic() - T_START:7.2f}s] {msg}\n")
    sys.stderr.flush()


# ------------------------------------------------------------ found by name

def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, path: str):
    name = f"benchmark_{kind}_{os.path.basename(path)[:-3]}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Files:
    """Where the benchmark's data files are. Tests point it at a
    temporary directory to show that a configuration, a mix and a metric
    are found by name."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = load_json(root, "BENCHMARK.json")
        self.dir = os.path.join(root, self.bench["paths"][0])

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"no workload [{name}] in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return load_json(self.root, c["file"])
        raise SystemExit(f"no config [{name}] in BENCHMARK.json")

    def builder(self, config: dict):
        return load_module("builder", os.path.join(
            self.dir, "configs", config["builder"] + ".py"))

    def traffic(self, name: str) -> dict:
        return load_json(self.dir, "traffic", name + ".json")

    def metrics_of(self, workload: str, group: str) -> list:
        """The cell's metrics of `end_to_end` or `per_layer`."""
        return [m for m in self.bench[group]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        spec = load_json(self.dir, "metrics", metric + ".json")
        mod = load_module("reader", os.path.join(
            self.dir, "metrics", "readers", spec["reader"] + ".py"))
        return mod.read, spec.get("params", {})


# ------------------------------------------------------------------ the run

def class_cycle(classes: list) -> list:
    """The fixed order in which a mix's query classes are sent: an even
    interleave of each class's `per_cycle` entries, the same for every
    seed. Request i is of class cycle[i % len(cycle)]."""
    slots = []
    for c_i, c in enumerate(classes):
        per = int(c["per_cycle"])
        slots += [((j + 0.5) / per, c_i) for j in range(per)]
    slots.sort()
    return [classes[c_i] for _, c_i in slots]


class Run:
    """What one run knows: the readers' whole input."""

    def __init__(self, files: Files, args):
        self.files = files
        self.args = args
        self.dry_run = args.dry_run
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.workload = files.workload(args.workload)
        self.config = files.config(self.workload["config"])
        traffic = files.traffic(self.workload["traffic"])
        if self.dry_run:
            traffic = {**traffic, **traffic.get("dry_run", {})}
        self.traffic = traffic
        self.batch = int(traffic.get("batch", 1))
        self.spans = {}             # name -> seconds, the harness's own
        self.compile_times = []     # monotonic time of each backend compile
        self.stats = {}             # "before"/"after" -> _nodes/stats node
        self.transfers = {}         # "before"/"after" -> transfer ledger
        self.samples = []           # counted requests (loadgen.Sample)
        self.all_samples = []       # and those that answered too late
        self.requests = []          # request index -> its queries
        self.window = (0.0, 0.0)    # monotonic start, end
        self.trace = None           # trace_reduce.Reduction, --trace 1
        self.trace_slice = None     # monotonic (start, stop) of the trace
        self.trace_called = None    # and when start_trace was called
        self.judged = 0
        self.mismatches = []
        self.seen = {}              # what the comparison read (oracle.py)
        self.node = self.server = self.corpus = self.jax = None

    # ---------------------------------------------------------- set-up

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.monotonic()
        yield
        self.spans[name] = time.monotonic() - t0
        log(f"{name}: {self.spans[name]:.2f}s")

    def start_node(self) -> None:
        from opensearch_tpu.launcher import start_node
        settings = {"http.port": 0, "node.name": "benchmark",
                    **self.config.get("node_settings", {})}
        self.node, self.server = start_node(settings)
        self.port = self.server.port

    def call(self, method: str, path: str, body=None) -> dict:
        """An administrative request outside the window; must answer 200."""
        conn = loadgen.Connection(self.port)
        try:
            status, raw = conn.request(
                method, path,
                json.dumps(body).encode() if body is not None else None)
        finally:
            conn.close()
        if status != 200:
            raise RuntimeError(f"{method} {path} -> {status}: {raw[:400]!r}")
        return json.loads(raw)

    def node_stats(self) -> dict:
        return next(iter(self.call("GET", "/_nodes/stats")["nodes"]
                         .values()))

    def install(self) -> None:
        """Pre-built sealed segments, installed the way chip_smoke.py's
        `Smoke.install` does: engine.install_segments + _sync_reader (the
        upload). The index is read-only from here on."""
        c = self.corpus
        self.call("PUT", f"/{c.index}", {"settings": c.index_settings,
                                         "mappings": c.mapping})
        svc = self.node.indices.get(c.index)
        if len(svc.shards) != len(c.segments):
            raise RuntimeError(f"{len(svc.shards)} shards for "
                               f"{len(c.segments)} segments")
        for shard, seg in zip(svc.shards, c.segments):
            shard.engine.install_segments([seg], max_seq_no=seg.num_docs,
                                          local_checkpoint=seg.num_docs)
            shard._sync_reader()
        # the upload is asynchronous until something reads the device
        self.jax.effects_barrier()
        for d in self.jax.live_arrays():
            d.block_until_ready()

    # --------------------------------------------------------- requests

    def path(self) -> str:
        return "/_msearch" if self.batch > 1 \
            else f"/{self.corpus.index}/_search"

    def content_type(self) -> str:
        return MSEARCH_TYPE if self.batch > 1 else SEARCH_TYPE

    def payload(self, queries: list) -> bytes:
        if self.batch == 1:
            return self.corpus.payload(queries[0])
        head = json.dumps({"index": self.corpus.index}).encode()
        return b"".join(head + b"\n" + self.corpus.payload(q) + b"\n"
                        for q in queries)

    def plan_requests(self) -> int:
        """How many requests the window can need."""
        t = self.traffic
        if t["loop"] == "open":
            return max(int(round(float(t["rate"]) * self.seconds)), 1)
        return max(int(float(t["provision_per_s"]) * self.seconds) + 1, 2)

    def draw(self):
        """Warm-up and window requests in one draw, so that no query of
        the window repeats a warm-up query. Request r holds the cycle's
        entries [r * batch, (r + 1) * batch): every batch has the same
        composition of classes in the same order."""
        cycle = class_cycle(self.traffic["classes"])
        if len(cycle) % self.batch and self.batch % len(cycle):
            raise SystemExit("the class cycle and the batch size must "
                             "divide one another")
        per_warm = max(len(cycle), self.batch)
        n_warm = int(self.traffic.get("warmup_rounds", 1)) \
            * (per_warm // self.batch)
        n_req = self.plan_requests()
        total = (n_warm + n_req) * self.batch
        classes = [cycle[i % len(cycle)] for i in range(total)]
        queries = self.corpus.draw(self.traffic.get("query", {}), classes,
                                   self.seed)
        reqs = [queries[i:i + self.batch]
                for i in range(0, total, self.batch)]
        return reqs[:n_warm], reqs[n_warm:]

    def warm_up(self, warm: list) -> None:
        """One request (or batch) of every class, then stop."""
        conn = loadgen.Connection(self.port)
        try:
            for queries in warm:
                status, raw = conn.post(self.path(), self.payload(queries),
                                        self.content_type())
                if status != 200:
                    raise RuntimeError(f"warm-up request -> {status}: "
                                       f"{raw[:300]!r}")
        finally:
            conn.close()

    # ----------------------------------------------------------- window

    def measure(self, payloads: list) -> None:
        t = self.traffic
        tracer = None
        if self.args.trace:
            tracer = threading.Thread(target=self._trace_slice,
                                      name="bench-trace")
        self.stats["before"] = self.node_stats()
        self.transfers["before"] = self.call(
            "GET", "/_telemetry/transfers")["transfers"]
        t0 = time.monotonic() + 1.5     # the child's start-up fits in
        t_end = t0 + self.seconds
        self.window = (t0, t_end)
        self.spans["setup_s"] = t0 - T_START
        common = dict(port=self.port, path=self.path(),
                      content_type=self.content_type(), payloads=payloads,
                      t0=t0)
        if t["loop"] == "open":
            child = loadgen.in_child(
                "open", threads=int(t["threads"]),
                offsets=loadgen.fixed_gaps(
                    len(payloads), float(t["rate"]),
                    int(t["schedule_seed"]), self.seed), **common)
        else:
            child = loadgen.in_child("closed", clients=int(t["clients"]),
                                     t_end=t_end, **common)
        if tracer is not None:
            tracer.start()
        samples = child.result()
        # open: every request due in the window counts, however late it
        # answered (a backlog shows as latency, not as a shorter list);
        # closed: a request counts if it completed inside the window
        self.samples = samples if t["loop"] == "open" \
            else [s for s in samples if s.done <= t_end]
        self.all_samples = samples
        self.drained = max(s.done for s in samples)
        if tracer is not None:
            tracer.join()
            if self.trace is None:      # the thread's traceback is above
                raise RuntimeError("the traced slice gave no reduction")
        self.stats["after"] = self.node_stats()
        self.transfers["after"] = self.call(
            "GET", "/_telemetry/transfers")["transfers"]

    def _trace_slice(self) -> None:
        """--trace 1: the device trace of a slice of the window, reduced
        by the benchmark's own code (trace_reduce.py)."""
        from benchmark import trace_reduce
        t0, t_end = self.window
        length = min(float(self.traffic["trace_seconds"]),
                     0.6 * self.seconds)
        start = t0 + min(1.0, 0.2 * self.seconds)
        time.sleep(max(start - time.monotonic(), 0))
        # device planes are all the reduction reads: no Python or host
        # tracer, which slowed the host path they would observe (call A,
        # PR 24). Those options are proven on the CPU backend only, so a
        # slice that comes back without a device plane on a TPU is taken
        # again with the profiler's defaults, which call A ran with.
        light = self.jax.profiler.ProfileOptions()
        light.python_tracer_level = 0
        light.host_tracer_level = 0
        on_tpu = self.jax.devices()[0].platform == "tpu"
        for options in (light, None):
            out = self.args.keep_trace \
                or tempfile.mkdtemp(prefix="bench-trace-")
            try:
                self.trace_called = time.monotonic()
                self.jax.profiler.start_trace(out, profiler_options=options)
                a = time.monotonic()
                time.sleep(length)
                b = time.monotonic()
                self.jax.profiler.stop_trace()
                # the host's reading of the slice; the device's own, from
                # its first recorded op to its last, is the window
                # wherever the planes can be laid on this clock
                # (trace_reduce.py, spans.py `recorded_intervals`)
                self.trace_slice = (a, b)
                self.trace = trace_reduce.reduce_file(
                    trace_reduce.find_xplane(out), window_s=b - a)
            except Exception as e:      # only the unproven options may fail
                if options is None:
                    raise
                log(f"light trace failed ({type(e).__name__}: {e})")
                continue
            finally:
                if not self.args.keep_trace:
                    shutil.rmtree(out, ignore_errors=True)
            if self.trace.planes or not on_tpu \
                    or time.monotonic() + length + 2.0 > t_end:
                break
            log("no device plane in the light trace: again with defaults")

    def keep_slice(self) -> None:
        """--keep-trace: beside the profile, what the span readers took
        from the same slice (the ring's rows of the requests near it and
        the client's samples), so that a kept trace can be read again
        off the chip (tests/benchmark/recorded)."""
        from benchmark import spans
        ring = spans.fetch(self)
        a, b = self.trace_slice
        lo, hi = a - 2.0, b + 1.0
        kept = {"trace_slice": [a, b], "trace_called": self.trace_called,
                "ring": None,
                "samples": [{"index": s.index, "sent": s.sent,
                             "done": s.done,
                             "queries": len(self.requests[s.index])}
                            for s in self.all_samples
                            if s.done >= lo and s.sent <= hi]}
        if ring is not None:
            near = {s["trace_id"] for s in ring.spans
                    if s["parent_id"] == 0 and s["end_ns"] >= lo * 1e9
                    and s["start_ns"] <= hi * 1e9}
            kept["ring"] = {"clock": "monotonic_ns", "dropped": ring.dropped,
                            "anchor": ring.anchor,
                            "spans": [s for s in ring.spans
                                      if s["trace_id"] in near]}
        with open(os.path.join(self.args.keep_trace, "slice.json"),
                  "w") as f:
            json.dump(kept, f)

    # ------------------------------------------------------------ judge

    def parse_and_judge(self) -> None:
        """Every response's status and items are checked; a seeded sample
        of pages goes to the plain reference. Runs after the window, on
        stored bytes."""
        from benchmark import oracle
        self.attempted = self.failed = 0
        pages = []      # (request index, position, query, response)
        for s in self.samples:
            queries = self.requests[s.index]
            self.attempted += len(queries)
            try:
                if s.status != 200:
                    raise oracle.Mismatch(f"status {s.status}")
                body = json.loads(s.raw)
                items = body["responses"] if self.batch > 1 else [body]
                if len(items) != len(queries):
                    raise oracle.Mismatch(f"{len(items)} items")
            except (ValueError, KeyError, oracle.Mismatch) as e:
                self.failed += len(queries)
                self.mismatches.append(f"request {s.index}: {e}")
                continue
            for pos, (q, item) in enumerate(zip(queries, items)):
                if "error" in item or item.get("status", 200) != 200:
                    self.failed += 1
                pages.append((s.index, pos, q, item))
        self.answered = self.attempted - self.failed
        want = int(self.traffic["judge"]["sample"])
        rng = random.Random(self.seed ^ 0x6a756467)
        if len(pages) > want:
            if self.batch > 1:
                # spread over all of the window's batches
                by_req = {}
                for p in pages:
                    by_req.setdefault(p[0], []).append(p)
                per = -(-want // len(by_req))
                pages = [p for ps in by_req.values()
                         for p in rng.sample(ps, min(per, len(ps)))]
            else:
                pages = rng.sample(pages, want)
        t0 = time.monotonic()
        bad = self.corpus.judge([(q, item) for _, _, q, item in pages],
                                self.seen)
        self.mismatches += bad
        self.judged = len(pages)
        log(f"judged {self.judged} pages in {time.monotonic() - t0:.2f}s, "
            f"{len(bad)} differ")

    def compared(self) -> dict:
        """Each number `correct` rests on beside its limit: the widest
        relative gap of a served score from the reference's (the limit
        is the configuration's own, `guarantees`), the judged pages that
        differ from the reference in any way (ids, order, total, a
        failed shard), and how many pages were judged at all."""
        out = {}
        if "score_rel_err_max" in self.seen:
            out["score_rel_err_max"] = {
                "value": self.seen["score_rel_err_max"],
                "limit": self.seen["score_rel_err_limit"],
                "holds": "at_most"}
        out["pages_differing"] = {"value": len(self.mismatches),
                                  "limit": 0, "holds": "at_most"}
        out["pages_judged"] = {"value": self.judged, "limit": 1,
                               "holds": "at_least"}
        return out

    # ---------------------------------------------------------- metrics

    def read_metrics(self, group: str) -> dict:
        out = {}
        for m in self.files.metrics_of(self.workload["name"], group):
            read, params = self.files.reader(m["name"])
            value = read(self, params)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def stop(self) -> None:
        if self.server is not None:
            self.server.close()


def mesh_width(node_stats: dict) -> int:
    """On how many devices the node holds a part of an SPMD shard set
    (`telemetry.device_memory`, class `spmd_shard_sets`, `by_device`):
    the width of the mesh the sharded requests of the window ran on, 0
    where none took the SPMD program."""
    image = node_stats["telemetry"]["device_memory"]["classes"].get(
        "spmd_shard_sets", {})
    return sum(1 for nbytes in image.get("by_device", {}).values()
               if nbytes > 0)


def sweep(run: Run, window_reqs: list, rates: list) -> None:
    """Find the highest sustained rate, once, when the cell is defined:
    each rate for `--seconds` after one warm-up; prints a table, no
    result line. Not part of a measured run."""
    rows = []
    lo = 0
    for rate in rates:
        n = max(int(round(rate * run.seconds)), 1)
        reqs = window_reqs[lo:lo + n]
        lo += n
        if len(reqs) < n:
            raise SystemExit("the sweep drew too few requests")
        payloads = [run.payload(q) for q in reqs]
        offsets = loadgen.fixed_gaps(n, rate,
                                     int(run.traffic["schedule_seed"]),
                                     run.seed)
        t0 = time.monotonic() + 1.5
        samples = loadgen.in_child(
            "open", port=run.port, path=run.path(),
            content_type=run.content_type(), payloads=payloads,
            offsets=offsets, threads=int(run.traffic["threads"]),
            t0=t0).result()
        lat = [(s.done - s.intended) * 1000 for s in samples]
        in_slot = sum(1 for s in samples if s.done <= t0 + run.seconds)
        rows.append({
            "rate": rate, "requests": n, "completed_in_slot": in_slot,
            "share_in_slot": in_slot / n,
            "ok": sum(1 for s in samples if s.status == 200),
            "p50_ms": loadgen.percentile(lat, 0.5),
            "p95_ms": loadgen.percentile(lat, 0.95),
            "late_p95_ms": loadgen.percentile(
                [(s.sent - s.intended) * 1000 for s in samples], 0.95)})
        log(f"sweep {rows[-1]}")
        time.sleep(1.0)     # let a backlog drain before the next rate
    print(json.dumps({"sweep": run.workload["name"], "rows": rows}))


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=10)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--dry-run", action="store_true",
                   help="tiny sizes on the CPU backend; needs "
                        "JAX_PLATFORMS=cpu set by the caller")
    p.add_argument("--sweep", default="",
                   help="comma-separated rates: run each for --seconds "
                        "and print a table (defining a cell)")
    p.add_argument("--keep-trace", default="",
                   help="keep the --trace 1 profile in this directory")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    files = Files()
    run = Run(files, args)
    import jax
    run.jax = jax
    devices = jax.devices()
    dev = devices[0]
    if args.dry_run:
        if os.environ.get("JAX_PLATFORMS") != "cpu" or dev.platform != "cpu":
            sys.stderr.write("benchmark: --dry-run is the CPU debug run; "
                             "set JAX_PLATFORMS=cpu yourself\n")
            return 2
        log("DRY RUN on the CPU backend at tiny sizes: no number below is "
            "a device metric")
    elif dev.platform != "tpu" or len(devices) < run.workload["chips"]:
        sys.stderr.write(
            f"benchmark: cell [{args.workload}] needs "
            f"{run.workload['chips']} TPU chip(s); jax found "
            f"{len(devices)} x {dev.platform} ({dev.device_kind}). "
            f"Refusing to run.\n")
        return 1

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            run.compile_times.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    try:
        with run.span("node_start_s"):
            run.start_node()
        with run.span("corpus_build_s"):
            run.corpus = files.builder(run.config).build(
                run.config, args.seed, args.dry_run)
        with run.span("install_upload_s"):
            run.install()
        with run.span("draw_s"):
            if args.sweep:
                # one draw serves every rate of the sweep
                rates = [float(r) for r in args.sweep.split(",")]
                need = sum(int(round(r * run.seconds)) + 1 for r in rates)
                run.traffic = {**run.traffic, "rate": need / run.seconds}
            warm, window_reqs = run.draw()
            run.requests = window_reqs
            payloads = [] if args.sweep \
                else [run.payload(q) for q in window_reqs]
        if args.trace:
            run.call("POST", "/_telemetry/transfers/_enable")
        with run.span("warmup_s"):
            run.warm_up(warm)
        if args.sweep:
            sweep(run, window_reqs, rates)
            return 0
        run.measure(payloads)
        # a cell that asks for several chips is there for what exists
        # only across them: it has to have run on a mesh of that width,
        # as the node itself reports it, not on one chip of the host
        chips = run.workload["chips"]
        width = mesh_width(run.stats["after"]) if chips > 1 else chips
        if width != chips:
            sys.stderr.write(
                f"benchmark: cell [{args.workload}] asks for {chips} "
                f"chips and its index is on a mesh of {width} after the "
                f"window (spmd_shard_sets.by_device). No result.\n")
            return 1
        if args.trace:
            # lay the planes on the host's clock before anything reads
            # the window or counts its requests
            from benchmark import spans
            spans.device_join(run)
            if args.keep_trace:
                run.keep_slice()
        log(f"window done: {len(run.samples)} requests counted; "
            f"setup {run.spans['setup_s']:.2f}s")
        run.parse_and_judge()
        group = "per_layer" if args.trace else "end_to_end"
        metrics = run.read_metrics(group)
    finally:
        run.stop()

    peak = 0
    for d in devices[:run.workload["chips"]]:
        peak = max(peak, (d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": not run.mismatches and run.judged > 0,
              "attempted": run.attempted, "failed": run.failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    for msg in run.mismatches[:10]:
        log(f"MISMATCH {msg}")
    log(f"spans {json.dumps({k: round(v, 3) for k, v in run.spans.items()})}"
        f" judged={run.judged} dry_run={args.dry_run}")
    # last on standard error and last in the line: what was compared
    result["compared"] = run.compared()
    for name, c in result["compared"].items():
        sign = "<=" if c["holds"] == "at_most" else ">="
        sys.stderr.write(f"[compared] {name} {c['value']!r} {sign} "
                         f"{c['limit']!r}\n")
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
