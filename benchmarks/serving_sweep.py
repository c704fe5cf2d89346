"""Serving sweep: SLO percentiles + Pareto fronts over scheduler policies.

Drives the trace-driven continuous-batching simulator (``core.serving``,
DESIGN.md §21) over zoo models x scheduler knobs.  Each model's phase
costs come from the §17 batched node engine (``build_zoo_cost_model``:
prefill µs/token + a decode-batch latency grid, disk-cached per
(arch, phase, batch) cell) and its KV working set from the REAL cache
pytree (``kv_token_bytes``); the open-loop Poisson arrival rate is set
to ``load_factor`` times the batch-1 service rate so batching headroom
is what the sweep measures.

    PYTHONPATH=src python -m benchmarks.serving_sweep          # full, needs zoo HLO
    PYTHONPATH=src python -m benchmarks.serving_sweep --quick  # synthetic, jax-free CI smoke

Full mode writes the committed ``BENCH_serving.json`` (schema: DESIGN.md
§16): per-model per-policy SLO metrics (p50/p99 TTFT, p50/p99 TPOT,
tokens/s/node) and the Pareto front over (p99 TTFT, -tokens/s).
``--quick`` writes ``BENCH_serving_quick.json`` from a synthetic cost
model — no jax, no HLO cache — and FAILS the build when the run blows
``--budget`` seconds, when batching stops paying (b=8 under 1.5x the
b=1 tokens/s), or when any run's Little's-law bookkeeping gap opens.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

from repro.core.serving import (LengthDist, ServingKnobs,
                                SyntheticCostModel, build_zoo_cost_model,
                                pareto_front, poisson_requests,
                                simulate_serving, traffic_for)

BENCH_JSON = Path("BENCH_serving.json")
QUICK_JSON = Path("BENCH_serving_quick.json")
HLO_CACHE = Path("experiments/zoo_hlo")
COST_CACHE = Path("experiments/serving_cost")
FULL_MODELS = ("chatglm3-6b", "qwen1.5-32b", "llama4-scout-17b-a16e",
               "mamba2-1.3b")
POLICIES = (
    ServingKnobs(max_batch=1),
    ServingKnobs(max_batch=8),
    ServingKnobs(max_batch=32),
    ServingKnobs(max_batch=32, admission="spf"),
    ServingKnobs(max_batch=32, prefill_chunk=256),
    ServingKnobs(max_batch=32, eviction="evict-oldest"),
)
N_REQUESTS = 600
LOAD_FACTOR = 2.5            # arrival rate as a multiple of the batch-1
                             # service rate: saturates b=1, leaves the
                             # batched policies finite headroom
SEED = 0
QUICK_BATCH_GAIN = 1.5       # b=8 must beat b=1 tokens/s by this factor


def batch1_service_time(cost, traffic: LengthDist) -> float:
    """Mean batch-1 service time: one prefill + (out-1) decode steps at
    the mean lengths — the rate anchor for the open-loop sweep."""
    p, o = traffic.prompt_mean, traffic.out_mean
    kv = cost.kv_bytes(1, p + o)
    return cost.prefill_time(int(p)) \
        + max(0.0, o - 1) * cost.decode_step_time(1, kv)


def sweep_model(cost, traffic: LengthDist, n: int, load: float,
                seed: int) -> dict:
    """Run every policy on one arrival trace; returns the per-model row
    (metrics per policy label + the Pareto front)."""
    s1 = batch1_service_time(cost, traffic)
    rate = load / s1
    reqs = poisson_requests(n, rate, traffic, seed=seed)
    metrics = {}
    for knobs in POLICIES:
        res = simulate_serving(reqs, cost, knobs)
        m = res.metrics()
        if m["little_law_gap"] >= 1e-6:
            raise SystemExit(f"Little's-law gap {m['little_law_gap']:.2e} "
                             f"at {knobs.label}: bookkeeping leak")
        metrics[knobs.label] = m
    labels = list(metrics)
    pts = [(metrics[lb]["p99_ttft_ms"], -metrics[lb]["tokens_per_s"])
           for lb in labels]
    return {
        "traffic": dataclasses.asdict(traffic),
        "rate_per_s": rate,
        "batch1_service_s": s1,
        "bytes_per_token": cost.bytes_per_token,
        "bytes_per_request": cost.bytes_per_request,
        "policies": metrics,
        "pareto": [labels[i] for i in pareto_front(pts)],
    }


def policy_rows() -> list:
    return [{"label": k.label, "max_batch": k.max_batch,
             "admission": k.admission, "prefill_chunk": k.prefill_chunk,
             "eviction": k.eviction} for k in POLICIES]


def run_quick(budget: float) -> dict:
    """Jax-free smoke: synthetic affine costs, two traffic mixes, full
    policy grid, with throughput/bookkeeping/wall gates."""
    t0 = time.perf_counter()
    # 20 kB/token keeps the mix compute-bound (realistic zoo KV scale);
    # at 1 MB/token the decode path is pure HBM streaming and batching
    # cannot pay by construction
    cost = SyntheticCostModel(prefill_t0=2e-4, prefill_per_token=1e-5,
                              decode_t0=1e-4, decode_per_seq=2e-5,
                              bytes_per_token=2e4, bytes_per_request=5e6)
    # both mixes are decode-weighted: batching only parallelizes decode
    # (prefill serializes an iteration), so a prompt-dominated mix caps
    # the b=8 gain at s1/prefill regardless of the scheduler
    mixes = {"chat": LengthDist(256, 0.8, 128, 0.6),
             "decode-heavy": LengthDist(512, 1.0, 256, 0.6)}
    models = {name: sweep_model(cost, tr, 2_000, LOAD_FACTOR, SEED)
              for name, tr in mixes.items()}
    wall = time.perf_counter() - t0
    for name, row in models.items():
        t1 = row["policies"]["fcfs_b1"]["tokens_per_s"]
        t8 = row["policies"]["fcfs_b8"]["tokens_per_s"]
        if t8 < QUICK_BATCH_GAIN * t1:
            raise SystemExit(f"{name}: b=8 tokens/s {t8:.0f} < "
                             f"{QUICK_BATCH_GAIN}x b=1 {t1:.0f}")
    if wall > budget:
        raise SystemExit(f"quick sweep took {wall:.1f}s > budget {budget}s")
    return {
        "schema": 1, "mode": "quick",
        "arrival": {"n_requests": 2_000, "load_factor": LOAD_FACTOR,
                    "seed": SEED},
        "policies": policy_rows(),
        "models": models,
        "wall_s": wall,
    }


def run_full(models, n: int) -> dict:
    t0 = time.perf_counter()
    rows = {}
    for arch in models:
        t1 = time.perf_counter()
        cost = build_zoo_cost_model(arch, hlo_cache_dir=HLO_CACHE,
                                    cost_cache_dir=COST_CACHE)
        rows[arch] = sweep_model(cost, traffic_for(arch), n,
                                 LOAD_FACTOR, SEED)
        rows[arch]["prefill_us_per_token"] = cost.prefill_per_token * 1e6
        rows[arch]["decode_grid_us"] = [[b, t * 1e6]
                                        for b, t in cost.decode_grid]
        print(f"{arch:28s} {time.perf_counter() - t1:6.1f}s  "
              f"pareto: {', '.join(rows[arch]['pareto'])}")
    return {
        "schema": 1, "mode": "full",
        "arrival": {"n_requests": n, "load_factor": LOAD_FACTOR,
                    "seed": SEED},
        "policies": policy_rows(),
        "models": rows,
        "wall_s": time.perf_counter() - t0,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="synthetic cost model, jax-free CI smoke")
    ap.add_argument("--budget", type=float, default=60.0,
                    help="--quick wall-clock budget in seconds")
    ap.add_argument("--models", nargs="*", default=list(FULL_MODELS))
    ap.add_argument("--n", type=int, default=N_REQUESTS,
                    help="requests per (model, policy) run")
    args = ap.parse_args()

    if args.quick:
        out = run_quick(args.budget)
        QUICK_JSON.write_text(json.dumps(out, indent=1))
        print(f"wrote {QUICK_JSON} ({out['wall_s']:.2f}s)")
        return

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    out = run_full(args.models, args.n)
    BENCH_JSON.write_text(json.dumps(out, indent=1))
    print(f"wrote {BENCH_JSON} ({out['wall_s']:.1f}s)")
    for arch, row in out["models"].items():
        best = max(row["policies"].items(),
                   key=lambda kv: kv[1]["tokens_per_s"])
        print(f"{arch:28s} best {best[0]:22s} "
              f"{best[1]['tokens_per_s']:9.1f} tok/s  "
              f"p99 TTFT {best[1]['p99_ttft_ms']:9.1f} ms")


if __name__ == "__main__":
    main()
