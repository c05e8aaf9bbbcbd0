"""Per-phase profile of bench config 1 (BM25 match msearch batch).

Where the msearch batch goes: host prep (parse/compile/pad) vs device
dispatch vs device compute vs transfer — plus microbenchmarks of the
kernel's building blocks (gather+BM25, dense scatter-add, full-width
top_k, and the candidate-buffer alternative) at the measured shapes, so an
optimization attacks the real bottleneck. Writes PROFILE_RUN.md at the
repo root; every wall is the host clock's and names the platform it ran on.

Usage:  python tools/profile_bench.py  [BENCH_DOCS=100000 BENCH_QUERIES=1024]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

RESULTS: list = []


def log(name, seconds, note=""):
    RESULTS.append((name, seconds, note))
    print(f"{name:44s} {seconds * 1000:10.1f} ms  {note}", flush=True)


def main():
    import bench
    platform = bench.require_device().platform
    import jax
    import jax.numpy as jnp
    print(f"platform: {platform}")

    from opensearch_tpu.utils.demo import query_terms

    t0 = time.perf_counter()
    executor, seg = bench.build_index()
    log("index build (host)", time.perf_counter() - t0)

    queries = query_terms(bench.N_QUERIES, bench.VOCAB, seed=7,
                          terms_per_query=2)
    bodies = [{"query": {"match": {"body": q}}, "size": bench.TOP_K}
              for q in queries]

    # ---- end-to-end: warm + timed run (what bench.py measures)
    t0 = time.perf_counter()
    executor.multi_search(bodies)
    log("msearch cold (compiles)", time.perf_counter() - t0)
    from opensearch_tpu.telemetry import TELEMETRY
    TELEMETRY.metrics.reset()
    # ledger ON for the whole profile: the per-stage timings below are
    # taken via ledger-attributed device_get, so the run's channel/wave
    # decomposition is real data
    TELEMETRY.ledger.enabled = True
    TELEMETRY.ledger.reset()
    t0 = time.perf_counter()
    executor.multi_search(bodies)
    total = time.perf_counter() - t0
    log("msearch warm TOTAL", total,
        f"{len(bodies) / total:.0f} QPS")
    snap = TELEMETRY.metrics.to_dict()
    for name, h in sorted(snap["histograms"].items()):
        if name.startswith("msearch.phase."):
            log(f"warm phase: {name[len('msearch.phase.'):-len('_ms')]}",
                h["sum_ms"] / 1000)
    print("interning counters:",
          {k: v for k, v in snap["counters"].items()
           if "template" in k or k == "search.plan_compiles"})

    # ---- dissect the warm path (mirrors multi_search's envelope path)
    from opensearch_tpu.search import dsl
    from opensearch_tpu.search.compile import Compiler
    from opensearch_tpu.search.executor import (_envelope_runner,
                                                pack_leaves,
                                                stack_flat_inputs)
    from opensearch_tpu.index.segment import pad_bucket
    from opensearch_tpu.parallel.distributed import (_tree_shapes,
                                                     plan_struct)

    t0 = time.perf_counter()
    stats = executor.reader.stats()
    compiler = Compiler(executor.reader.mapper, stats)
    compiled = []
    for body in bodies:
        node = dsl.parse_query(body["query"])
        compiled.append(compiler.compile(
            node, executor.reader.segments[0], executor.reader.device[0][1]))
    log("host: parse+compile plans", time.perf_counter() - t0,
        f"{len(bodies)} plans")

    t0 = time.perf_counter()
    flats_all = [p.flatten_inputs([]) for p in compiled]
    groups = {}
    for i, p in enumerate(compiled):
        groups.setdefault((plan_struct(p), _tree_shapes(flats_all[i])),
                          []).append(i)
    log("host: flatten+group", time.perf_counter() - t0,
        f"{len(groups)} group(s)")

    arrays, meta = executor.reader.device[0]
    group_stats = []
    t_stack = t_pack = t_upload = t_disp = 0.0
    pending = []
    for (struct, shapes), idxs in groups.items():
        b_pad = pad_bucket(len(idxs), minimum=1)
        t0 = time.perf_counter()
        group_flats = [flats_all[i] for i in idxs]
        group_flats += [group_flats[0]] * (b_pad - len(idxs))
        stacked, treedef, _axes = stack_flat_inputs(group_flats)
        stacked.append(np.full(b_pad, -1e38, np.float32))
        t_stack += time.perf_counter() - t0
        t0 = time.perf_counter()
        buf, layout = pack_leaves(stacked)
        t_pack += time.perf_counter() - t0
        t0 = time.perf_counter()
        dev_buf = jnp.asarray(buf)
        t_upload += time.perf_counter() - t0
        plan0 = compiled[idxs[0]]
        fn = _envelope_runner(plan_struct(plan0), plan0, meta, 10,
                              layout, treedef)
        t0 = time.perf_counter()
        pending.append(fn(arrays, dev_buf))
        t_disp += time.perf_counter() - t0
        group_stats.append((len(idxs), b_pad, buf.nbytes))
    log("host: stack", t_stack)
    log("host: pack envelope", t_pack)
    log("host: upload (asarray calls)", t_upload,
        f"{sum(g[2] for g in group_stats)} B")
    log("host: dispatch (async calls)", t_disp)
    # Stage boundary measured via a LEDGER-ATTRIBUTED device_get: one
    # attributed fetch charges execute + transfer to one number, and
    # the ledger records it like any serving-path collect.
    ledger = TELEMETRY.ledger
    t0 = time.perf_counter()
    with ledger.attributed():
        fetched = jax.device_get(pending)
    collect_s = time.perf_counter() - t0
    fetched_b = sum(np.asarray(f).nbytes for f in fetched)
    ledger.note_device_get(collect_s * 1000, nbytes=fetched_b)
    log("device+transfer: attributed device_get", collect_s,
        f"{fetched_b} B (execute+fetch)")

    d_pad = int(arrays["live"].shape[0])
    b_total = sum(b for b, _, _ in group_stats)
    qb_max = 0
    for (struct, shapes), _ in groups.items():
        for _, shp, _dt in shapes:
            if len(shp) == 1:
                qb_max = max(qb_max, shp[0])
    print(f"\ngroups (n, b_pad, bytes): {group_stats}  d_pad={d_pad} "
          f"qb_max={qb_max}")

    # ---- microbenchmarks at representative shapes
    B = min(b_total, 1024)
    QB = max(qb_max, 16)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, arrays["post_docs"].shape[0],
                                  size=(B, QB)), dtype=jnp.int32)
    w = jnp.asarray(rng.rand(B, QB), dtype=jnp.float32)

    def timed(fn, *args, reps=3, name="", note=""):
        """Microbench via ledger-attributed device_get — the same sync
        the serving path's collect pays, so stage walls add up to the
        batch's."""
        out = fn(*args)
        with ledger.attributed():
            jax.device_get(out)                 # warm (compile) pass
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(*args)
                jax.device_get(out)
            dt = (time.perf_counter() - t0) / reps
        ledger.note_device_get(dt * 1000)
        log(name, dt, note)

    post_docs, post_tf = arrays["post_docs"], arrays["post_tf"]

    @jax.jit
    def k_gather(ids, w):
        docs = post_docs[ids]                       # [B, QB, 128]
        tfs = post_tf[ids]
        part = w[:, :, None] * tfs / (tfs + 1.2)
        return part.sum(axis=(1, 2))

    timed(k_gather, ids, w, name="μ: gather+bm25 (no scatter)",
          note=f"[B={B},QB={QB},128]")

    @jax.jit
    def k_scatter(ids, w):
        docs = post_docs[ids]
        tfs = post_tf[ids]
        part = w[:, :, None] * tfs / (tfs + 1.2)
        valid = docs >= 0
        sidx = jnp.where(valid, docs, d_pad)

        def one(s, p):
            return jnp.zeros(d_pad, jnp.float32).at[s.ravel()].add(
                p.ravel(), mode="drop")
        return jax.vmap(one)(sidx, jnp.where(valid, part, 0.0))

    timed(k_scatter, ids, w, name="μ: + dense scatter [B,d_pad]",
          note=f"out {B}x{d_pad}")

    @jax.jit
    def k_scatter_topk(ids, w):
        dense = k_scatter(ids, w)
        return jax.lax.top_k(dense, 10)

    timed(k_scatter_topk, ids, w, name="μ: + full-width top_k(10)")

    @jax.jit
    def k_scatter_topk2(ids, w):
        dense = k_scatter(ids, w)
        rows = dense.reshape(B, d_pad // 128, 128)
        part_v, part_i = jax.lax.top_k(rows, 10)        # [B, R, 10]
        base = (jnp.arange(d_pad // 128) * 128)[None, :, None]
        flat_v = part_v.reshape(B, -1)
        flat_i = (part_i + base).reshape(B, -1)
        v, j = jax.lax.top_k(flat_v, 10)
        return v, jnp.take_along_axis(flat_i, j, axis=1)

    timed(k_scatter_topk2, ids, w, name="μ: + two-stage top_k(10)")

    # candidate-buffer alternative: sort postings lanes by doc id,
    # segment-sum duplicates, top-k over the compact buffer
    @jax.jit
    def k_candidates(ids, w):
        docs = post_docs[ids].reshape(B, -1)            # [B, N]
        tfs = post_tf[ids].reshape(B, -1)
        part = jnp.where(docs >= 0,
                         w.repeat(128, axis=1) * tfs / (tfs + 1.2), 0.0)
        big = jnp.where(docs >= 0, docs, 2 ** 30)
        sdocs, spart = jax.lax.sort([big, part], num_keys=1)
        csum = jnp.cumsum(spart, axis=1)
        n = sdocs.shape[1]
        last = jnp.concatenate([sdocs[:, :-1] != sdocs[:, 1:],
                                jnp.ones((B, 1), bool)], axis=1)
        run_total = jnp.where(
            last, csum - jnp.concatenate(
                [jnp.zeros((B, 1), jnp.float32),
                 jnp.where(last, csum, 0.0)[:, :-1]], axis=1), 0.0)
        # (approx for the μbench: mask non-run-ends, topk over N)
        masked = jnp.where(last & (sdocs < 2 ** 30), csum, -1e38)
        v, j = jax.lax.top_k(masked, 10)
        return v, jnp.take_along_axis(sdocs, j, axis=1)

    timed(k_candidates, ids, w,
          name="μ: candidate-buffer (sort+segsum+topk)",
          note=f"N={QB * 128}")

    # raw run dump (git-ignored: a profile is a run's output, not a
    # record of the repository)
    lsnap = TELEMETRY.ledger.snapshot()
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "PROFILE_RUN.md"), "w") as f:
        f.write("# bench config 1 profile run (%s)\n\n" % platform)
        f.write("All device-stage timings are ledger-attributed "
                "`device_get` walls on the host clock.\n\n")
        f.write("| phase | ms | note |\n|---|---|---|\n")
        for name, sec, note in RESULTS:
            f.write(f"| {name} | {sec * 1000:.1f} | {note} |\n")
        f.write(f"\ngroups (n, b_pad, bytes): {group_stats}; "
                f"d_pad={d_pad}; qb_max={qb_max}; B={B}\n")
        f.write(f"\nledger: waves={lsnap['waves']} "
                f"device_get={lsnap['device_get']} "
                f"pipeline={lsnap['pipeline']}\n")
    print("\nwrote PROFILE_RUN.md")


if __name__ == "__main__":
    main()
