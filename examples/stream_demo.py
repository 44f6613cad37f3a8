"""Online ICOA demo: stream ~1M instances from a drifting source while a
concurrent request thread serves ensemble predictions off the live weights.

The main thread runs `stream_fit` — ingest (rank-1 ring-buffer commits),
cadenced re-sweeps, checkpoints — and publishes fresh (params, weights) to a
`PredictEngine` after every chunk.  A daemon thread hammers
`engine.predict()` the whole time, exactly the serving topology DESIGN.md
§11 describes: requests never wait on training, they read whatever state was
last published.

    PYTHONPATH=src python examples/stream_demo.py                 # ~1M rows
    PYTHONPATH=src python examples/stream_demo.py --instances 65536
"""
import argparse
import tempfile
import threading
import time

import numpy as np

from repro import api
from repro.stream import PredictEngine, latest_stream_step, stream_fit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=1_000_000)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--resweep-every", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=32,
                    help="request batch size for the serving thread")
    args = ap.parse_args()
    total = (args.instances // args.chunk) * args.chunk

    spec = api.StreamSpec(
        experiment=api.ExperimentSpec(
            data=api.DataSpec(source="cosine", n_train=args.window,
                              n_test=args.window),
            solver=api.SolverSpec(name="icoa", engine="fused")),
        window=args.window, chunk=args.chunk, total_instances=total,
        resweep_every=args.resweep_every,
        drift_option="freq", drift_start=1.0, drift_end=2.0,
        checkpoint_every=(total // 4 // args.chunk) * args.chunk or None,
        serve_buckets=(1, args.batch, 4 * args.batch))

    n_attrs = spec.experiment.data.resolved_n_attrs
    groups = spec.experiment.data.groups
    family = spec.experiment.agent.resolve(n_cols=len(groups[0]))
    engine = PredictEngine(family, groups, n_attrs, spec.serve_buckets)

    # no ad-hoc stopwatches here: the engine's own obs.health rings/counters
    # (the same ones the metrics_text scrape reads) ARE the
    # latency/throughput record — the request thread just drives traffic
    stop = threading.Event()

    def request_loop():
        rng = np.random.default_rng(0)
        while engine._params is None and not stop.is_set():
            time.sleep(0.001)               # engine goes live on first update
        x = rng.uniform(-1.0, 1.0, size=(args.batch, n_attrs)) \
            .astype(np.float32)
        while not stop.is_set():
            engine.predict(x)

    thread = threading.Thread(target=request_loop, daemon=True)
    thread.start()

    ckdir = tempfile.mkdtemp(prefix="stream_demo_ck_")
    print(f"streaming {total:,} instances "
          f"(window={args.window}, resweep every {args.resweep_every}, "
          f"drift freq 1.0->2.0, checkpoints -> {ckdir})")
    t0 = time.perf_counter()
    res = stream_fit(spec, checkpoint_dir=ckdir, engine=engine)
    wall = time.perf_counter() - t0
    stop.set()
    thread.join(timeout=5.0)

    ing_rate = res.ingestor.counters["ingest_instances"].rate
    print(f"\ndone in {wall:.1f}s  "
          f"({res.ingestor.counters['ingest_instances'].total:,} instances "
          f"ingested at {ing_rate:,.0f}/sec, "
          f"{res.ingestor.counters['resweeps'].total} re-sweeps "
          f"({res.ingestor.counters['resweep_sweeps'].total} sweeps), "
          f"{res.total_bytes:,} re-sweep bytes metered)")
    print(f"last checkpoint: step {latest_stream_step(ckdir)} in {ckdir}")

    print("\n  count      train_mse   preq_mse    eta")
    recs = res.records
    shown = recs[:3] + ([None] if len(recs) > 6 else []) + recs[-3:] \
        if len(recs) > 6 else recs
    for r in shown:
        if r is None:
            print("  ...")
            continue
        print(f"  {r['count']:>9,}  {r['train_mse']:.6f}    "
              f"{r['preq_mse']:.6f}    {r['eta']:.4f}")

    # serving stats straight from the engine's histograms/counters
    reqs = engine.requests
    if reqs.total:
        pct = engine.latency[engine._bucket(args.batch)].percentiles()
        print(f"\nserved {reqs.total * args.batch:,} predictions "
              f"concurrently ({reqs.rate * args.batch:,.0f}/sec): latency "
              f"p50 {pct['p50'] * 1e6:.0f}us  p95 {pct['p95'] * 1e6:.0f}us  "
              f"p99 {pct['p99'] * 1e6:.0f}us")

    print("\nprometheus scrape (engine + ingestor health):")
    for line in engine.metrics_text(res.ingestor).splitlines():
        if not line.startswith("#"):
            print("  " + line)


if __name__ == "__main__":
    main()
