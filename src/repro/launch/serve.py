"""Serving launcher CLI: loads a model with seeded random weights and runs
continuous batched decode over a seeded synthetic request stream,
reporting tokens/s.

  PYTHONPATH=src python -m repro.launch.serve --arch musicgen-medium:smoke \
      --requests 8 --new-tokens 16

``make_requests`` and ``generate_all`` are the same path called
in-process (``chip_smoke.py`` drives the full-width server through them).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine


def make_requests(cfg, n_requests: int, prompt_len: int, new_tokens: int,
                  temperature: float = 0.0, seed: int = 0) -> List[Request]:
    """``n_requests`` requests with uniform random prompts drawn from
    ``seed``."""
    rng = np.random.default_rng(seed)
    requests = []
    for rid in range(n_requests):
        if cfg.input_mode == "codebooks":
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=(prompt_len, cfg.n_codebooks),
                                  dtype=np.int32)
        else:
            prompt = rng.integers(0, cfg.vocab_size, size=prompt_len,
                                  dtype=np.int32)
        requests.append(Request(rid=rid, prompt=prompt,
                                max_new_tokens=new_tokens,
                                temperature=temperature))
    return requests


def generate_all(engine: ServeEngine, requests
                 ) -> Tuple[Dict[int, list], float]:
    """Stream every request to completion: ``({rid: tokens}, seconds)``."""
    t0 = time.perf_counter()
    streamed: Dict[int, list] = {}
    for rid, token in engine.generate(requests):
        streamed.setdefault(rid, []).append(token)
    return streamed, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    params = T.init_params(cfg, seed=0)
    engine = ServeEngine(cfg, params, n_slots=args.slots,
                         cache_len=args.cache_len)
    requests = make_requests(cfg, args.requests, args.prompt_len,
                             args.new_tokens, args.temperature)
    streamed, dt = generate_all(engine, requests)
    total_new = sum(len(toks) for toks in streamed.values())
    print(f"[serve] {len(streamed)}/{args.requests} requests, "
          f"{total_new} new tokens in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s)")
    print(f"[serve] scheduler: {engine.scheduler.step_idx} engine steps, "
          f"{engine.scheduler.prefix_hits} prefix-cache hits "
          f"({engine.scheduler.prefix_tokens_reused} tokens reused)")
    if engine.paged_kv is not None:
        rep = engine.paged_kv.report()
        print(f"[serve] paged KV: resident "
              f"{rep['resident_bytes_total']} B, offloaded "
              f"{rep['offload_bytes_total']} B over "
              f"{len(rep['groups'])} layer group(s)")
    for rid in sorted(streamed)[:3]:
        print(f"  rid={rid} first-tokens={streamed[rid][:8]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
