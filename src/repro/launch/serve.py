"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Drives the continuous-batching serving engine (per-tick admit/evict,
fused chunked prefill, greedy decode; ``--scheduling fixed`` for the
legacy batch-synchronous baseline) over synthetic requests and reports
throughput.
"""
from __future__ import annotations

import argparse

from repro.configs import ARCH_IDS, get_config
from repro.data.synthetic import serving_requests
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import ServingEngine
from repro.serve.scheduler import POLICIES
from repro.train.loop import init_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(ARCH_IDS))
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--scheduling", choices=list(POLICIES),
                    default="continuous")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="max prompt tokens fused per compiled step")
    ap.add_argument("--full", action="store_true",
                    help="use the full (not smoke) config at its published "
                         "widths")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    if cfg.is_encoder_decoder:
        raise SystemExit("serve driver targets decoder-only archs")
    params = init_model(cfg, seed=0)
    engine = ServingEngine(cfg, params, batch_slots=args.slots,
                           cache_len=args.cache_len,
                           scheduling=args.scheduling,
                           prefill_chunk=args.prefill_chunk)
    reqs = list(serving_requests(cfg.vocab_size, args.requests,
                                 max_prompt=args.max_prompt,
                                 max_new=args.max_new, seed=0))
    engine.submit(reqs)
    done = engine.run()
    dt = engine.report()["tick_s"]     # wall seconds from the registry
    total_tokens = sum(len(v) for v in done.values())
    print(f"[serve] arch={cfg.name} completed {len(done)}/{len(reqs)} "
          f"requests, {total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens / max(dt, 1e-9):.1f} tok/s)")
    for rid in sorted(done)[:5]:
        print(f"  req {rid}: {done[rid]}")


if __name__ == "__main__":
    main()
