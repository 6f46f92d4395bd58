"""Multi-tenant OCL serving CLI over ``repro.serve.FerretServer``.

Admits ``--tenants`` same-architecture sessions — each with its own
drifting token stream, OCL algorithm, and weighted share of one device
memory pool — and drives them to completion through the shared server:
one bucketed engine cache (compile count < tenant count proves the
same-geometry sharing), deficit-round-robin segment scheduling, live pool
re-division as tenants finish.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --tenants 4 --rounds 64 \
      --arch h2o-danube-1.8b --smoke --budget-gb 4
  PYTHONPATH=src python -m repro.launch.serve --tenants 2 \
      --algorithm er --scheduler rr

The former ``repro.launch.serve`` (batched prefill + decode token
generation) lives at ``repro.launch.generate``; invocations using its
flags (``--gen`` / ``--prompt-len``) are forwarded there with a
``DeprecationWarning``.
"""

from __future__ import annotations

import argparse
import math
import sys
import time
import warnings

import numpy as np

_GENERATE_FLAGS = ("--gen", "--prompt-len", "--temperature")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    if any(flag in argv or any(a.startswith(flag + "=") for a in argv)
           for flag in _GENERATE_FLAGS):
        warnings.warn(
            "token generation moved from repro.launch.serve to "
            "repro.launch.generate — forwarding this invocation; switch to "
            "`python -m repro.launch.generate`",
            DeprecationWarning, stacklevel=2,
        )
        from repro.launch import generate

        sys.argv = [sys.argv[0], *argv]
        generate.main()
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--arch", default=None, help="registered architecture name "
                    "(default: a small built-in benchmark LM)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rounds", type=int, default=48, help="stream rounds per tenant")
    ap.add_argument("--segment-rounds", type=int, default=8)
    ap.add_argument("--budget-gb", type=float, default=0.0,
                    help="global pool; 0 = unconstrained (every tenant M+)")
    ap.add_argument("--algorithm", default="vanilla")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--scheduler", default="drr", choices=["drr", "rr"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.models.config import ModelConfig
    from repro.models.registry import get_config
    from repro.ocl.streams import StreamConfig, make_stream
    from repro.serve import FerretServer, RoundRobinScheduler

    if args.arch is not None:
        cfg = get_config(args.arch, smoke=args.smoke)
        vocab = min(cfg.vocab_size, 64)
    else:
        vocab = 32
        cfg = ModelConfig(
            name="serve-lm", family="dense", num_layers=4, d_model=64,
            num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=vocab,
            compute_dtype="float32",
        )

    budget = math.inf if args.budget_gb <= 0 else args.budget_gb * 2**30
    scheduler = RoundRobinScheduler() if args.scheduler == "rr" else None
    server = FerretServer(
        budget, scheduler=scheduler, segment_rounds=args.segment_rounds,
        smoke=True,
    )
    for i in range(args.tenants):
        stream = make_stream(StreamConfig(
            kind="drift", modality="tokens", length=args.rounds,
            batch=args.batch, vocab=vocab, seq=args.seq, seed=args.seed + i,
        ))
        for k in ("tokens", "labels"):
            stream[k] = stream[k] % cfg.vocab_size
        server.admit(
            cfg, args.algorithm, stream, name=f"tenant{i}",
            batch=args.batch, seq=args.seq, lr=args.lr,
            max_workers=3, max_stages=4, seed=args.seed + i,
        )
    print(f"admitted {args.tenants} tenants "
          f"(pool={'inf' if math.isinf(budget) else f'{args.budget_gb:g}GiB'}, "
          f"scheduler={args.scheduler})")

    t0 = time.time()
    results = server.serve()
    dt = time.time() - t0

    total_rounds = sum(r.rounds for r in results.values())
    for name in sorted(results):
        print(f"  {name}: {results[name].summary()}")
    print(
        f"{len(results)} tenants, {total_rounds} rounds in {dt:.1f}s "
        f"({total_rounds / dt:.1f} rounds/s sustained); engine compiles="
        f"{server.compile_count} (< {args.tenants} tenants: shared), "
        f"cache hits={server.engine_cache.hits}"
    )
    accs = np.array([r.online_acc for r in results.values()])
    print(f"online acc mean={accs.mean():.4f} min={accs.min():.4f} "
          f"max={accs.max():.4f}")


if __name__ == "__main__":
    main()
