"""Serving driver: continuous-batching engine on the scheduler runtime.

CPU smoke example:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --requests 12 --slots 4

``--mode admission`` runs the pre-runtime baseline (no steal/rebalance);
``--stub`` swaps the model for the deterministic numpy stub (no jit) —
the pure-scheduler smoke the CI serving benchmark uses.

``--open-loop`` switches from the closed synthetic batch to an open-loop
arrival trace (``--rate``, ``--trace-steps``, ``--process``): requests
arrive on their own clock with SLA classes and heavy-tailed lengths, and
the run prints per-class TTFT/per-token percentiles plus goodput-under-
SLA.  ``--sla`` (default with --open-loop) schedules by class (WDRR
admission + demotion; add ``--preempt`` to let interactive backlog park
batch gangs); ``--no-sla`` is the hold-the-slot FIFO baseline judged by
the same SLOs.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.serving import (SLA_CLASSES, ServingEngine, StubModelBackend,
                           drive, make_trace)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", default="runtime",
                    choices=("runtime", "admission"))
    ap.add_argument("--stub", action="store_true",
                    help="deterministic numpy model stub (no jit compile)")
    ap.add_argument("--pods", type=int, default=1,
                    help="shard the slot fleet across this many pods "
                         "(DCN-priced steals between them)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="hosts per pod; gangs are routed home round-robin")
    ap.add_argument("--hbm-budget", type=float, default=None,
                    help="KV byte budget per page group (1 unit = 1 "
                         "resident request); full groups refuse loot")
    ap.add_argument("--per-host-decode", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="drive one decode_step per host batch (one jit "
                         "per host, per-host step/occupancy ledgers); "
                         "--no-per-host-decode falls back to the single "
                         "global batch.  Streams are identical either way")
    ap.add_argument("--wave-prefill", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="prefill same-length fresh prompts of one "
                         "admission wave in a single batched call per "
                         "host; --no-wave-prefill runs the per-request "
                         "prefill loop.  Streams are identical either way")
    ap.add_argument("--dcn-rebalance", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="quote re-spreads per boundary crossed and buy "
                         "host-local ones when machine-wide moves are "
                         "overpriced; --no-dcn-rebalance keeps the "
                         "flat-quoted machine-wide re-spread")
    ap.add_argument("--open-loop", action="store_true",
                    help="drive an open-loop arrival trace (SLA classes, "
                         "heavy-tailed lengths) instead of the closed "
                         "synthetic batch; prints per-class latency "
                         "percentiles and goodput-under-SLA")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="open-loop mean arrivals per engine step")
    ap.add_argument("--trace-steps", type=int, default=96,
                    help="open-loop arrival window in engine steps")
    ap.add_argument("--process", default="poisson",
                    choices=("poisson", "bursty", "diurnal"),
                    help="open-loop arrival process")
    ap.add_argument("--sla", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="schedule open-loop traffic by SLA class (WDRR "
                         "admission + multilevel-feedback demotion); "
                         "--no-sla holds slots in arrival order (FIFO "
                         "baseline, judged by the same SLOs)")
    ap.add_argument("--preempt", action="store_true",
                    help="let interactive backlog park a batch-tier "
                         "gang's KV (park/splice, no re-prefill) when "
                         "every slot is held (needs --sla)")
    args = ap.parse_args(argv)

    if args.stub:
        cfg = params = None
        backend = StubModelBackend()
    else:
        import jax
        from repro.configs import ARCHS, get_config
        from repro.launch.compile_cache import use_compile_cache
        from repro.models import api
        if args.arch not in ARCHS:
            raise SystemExit(f"unknown arch {args.arch!r}")
        use_compile_cache()
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        if cfg.enc_layers:
            raise SystemExit("enc-dec serving path: use examples/serve_batch.py")
        params = api.init(cfg, jax.random.PRNGKey(args.seed))
        backend = None                     # default JaxModelBackend

    rng = np.random.default_rng(args.seed)
    vocab = cfg.vocab if cfg is not None else 251
    sla = SLA_CLASSES if (args.open_loop and args.sla) else None
    eng = ServingEngine(cfg, params, n_slots=args.slots,
                        cache_len=args.cache_len, backend=backend,
                        mode=args.mode, pods=args.pods, hosts=args.hosts,
                        hbm_budget=args.hbm_budget,
                        per_host_decode=args.per_host_decode,
                        wave_prefill=args.wave_prefill,
                        dcn_rebalance=args.dcn_rebalance,
                        sla_classes=sla, preempt=args.preempt)

    if args.open_loop:
        trace = make_trace(steps=args.trace_steps, rate=args.rate,
                           seed=args.seed, process=args.process,
                           vocab=vocab)
        t0 = time.time()
        drive(eng, trace)
        dt = time.time() - t0
        toks = sum(len(r.out_tokens) for r in eng.completed)
        print(f"open-loop: {len(eng.completed)}/{len(trace)} requests, "
              f"{toks} tokens in {dt:.1f}s ({eng.steps} engine steps, "
              f"{'sla' if sla else 'fifo'} admission)")
        summary = eng.latency_summary()
        for name, row in sorted(summary["classes"].items()):
            print(f"  {name:<12} n={row['n']:<4} "
                  f"ttft p50/p99 {row['ttft_p50']:.0f}/{row['ttft_p99']:.0f} "
                  f"tok p50/p99 {row['tok_p50']:.1f}/{row['tok_p99']:.1f}")
        g = summary["goodput"]
        print(f"  goodput-under-SLA {g['good']}/{g['total']} "
              f"({g['frac']:.3f})")
        print("counters:", eng.counters())
        assert len(eng.completed) == len(trace)
        return 0

    n_hosts = args.pods * args.hosts
    homes = [c.name for c in eng.topo.components("host")] \
        if n_hosts > 1 else [None]

    t0 = time.time()
    for i in range(args.requests):
        prompt = rng.integers(1, vocab, size=args.prompt_len)
        # every 4th request pair shares a gang (prefix-affine group);
        # gangs are routed to a home host round-robin (cross-host
        # admission), lone requests stay on the global list
        gang = f"g{i//4}" if i % 2 == 0 else None
        home = homes[(i // 4) % len(homes)] if gang is not None else None
        eng.submit(prompt, args.new_tokens, prio=i % 3, gang=gang,
                   home=home)

    done = eng.run(max_steps=args.requests * args.new_tokens * 4)
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"completed {len(done)}/{args.requests} requests, "
          f"{toks} tokens in {dt:.1f}s "
          f"({toks/max(dt,1e-9):.1f} tok/s, {eng.steps} engine steps)")
    print("counters:", eng.counters())
    # per-host execution ledger: decode calls each host batch actually ran
    # and its mean occupancy — the skew view per-host decode exists for
    for h, (calls, occ) in enumerate(zip(eng.stats.host_decode_steps,
                                         eng.stats.host_active_slots)):
        lo, hi = eng._exec_groups[h]
        mean = occ / calls if calls else 0.0
        print(f"  host batch {h} (slots {lo}-{hi - 1}): "
              f"{calls} decode steps, mean occupancy {mean:.2f}")
    assert len(done) == args.requests
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
