"""Find the highest arrival rate a served cell sustains.

    python3 bench/sweep.py --workload <cell> --rates 0.25,0.35 \\
        --seeds 1,2,3 --seconds 51

For each rate and seed, one window of the cell's traffic at that rate
(``block`` scaled so that a block spans ``--seconds``), in one process;
no check.  One JSON line per window: the time to first token (median,
90th percentile and largest), the median of the first and of the second
half of the requests by due time (a backlog shows as a second half that
waits longer), and the requests without a first token.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness, model, serve
    bench = harness.load_benchmark()
    w = harness.workload(bench, args.workload)
    devs = harness.require_devices(w["chips"])[:w["chips"]]
    harness.compile_cache()
    cfg = model.load_config(w["config"])
    base = harness.load_traffic(w["traffic"])
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    for rate in (float(r) for r in args.rates.split(",")):
        block = max(1, round(rate * args.seconds))
        traffic = dict(base, rate_per_s=block / (args.seconds + 0.05),
                       block=block, check=dict(base["check"], min_tokens=0))
        for seed in (int(s) for s in args.seeds.split(",")):
            job = serve.Job(cfg, traffic, seed, args.seconds, devs, log)
            job.run()
            win = job.window
            e = job.end_to_end()
            due = sorted((t for t in win["tracked"]
                          if t.req.due < win["T"]), key=lambda t: t.req.due)
            ttft = [1e3 * ((t.first if t.first is not None else win["stop"])
                           - t.req.due) for t in due]
            h = len(ttft) // 2
            print(json.dumps({
                "rate": traffic["rate_per_s"], "block": block, "seed": seed,
                "requests": len(ttft), "failed": e["_failed"],
                "ttft_p50_ms": serve.percentile(ttft, 50),
                "ttft_p90_ms": serve.percentile(ttft, 90),
                "ttft_max_ms": max(ttft),
                "ttft_p50_first_half_ms": serve.percentile(ttft[:h], 50),
                "ttft_p50_second_half_ms": serve.percentile(ttft[h:], 50),
                "itl_p95_ms": e["itl_p95_ms"], "tokens": e["_tokens"]}),
                flush=True)
            job.release()
            del job
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
