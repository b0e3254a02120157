"""Serving launcher: thin CLI over the two ``Session`` serving paths.

Recsys archs (``dlrm-*``) route to the embedding inference subsystem
(``repro.serve``: frozen store view + window-coalescing batcher):

    python -m repro.launch.serve --arch dlrm-cached --store cached \
        --requests 256 --max-batch 32 --max-wait-ms 2 --zipf-a 2.5

LLM registry archs keep the batched prefill + KV-cache decode path:

    python -m repro.launch.serve --arch stablelm-3b --reduced \
        --batch 4 --prompt-len 16 --gen 8
"""
from __future__ import annotations

import argparse
import json

from ..api import Session
from ..configs.registry import get_arch
from .compile_cache import enable_compile_cache


def serve(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    # LLM decode path
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=8)
    # recsys embedding-serving path
    p.add_argument("--store", default="auto",
                   help="embedding tier: device | host | cached | auto")
    p.add_argument("--requests", type=int, default=256)
    p.add_argument("--max-batch", type=int, default=32,
                   help="window size (requests coalesced per dispatch)")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="latency bound: oldest queued request waits at most this")
    p.add_argument("--zipf-a", type=float, default=None,
                   help="request-key skew (default: the arch's training zipf_a)")
    p.add_argument("--qps", type=float, default=None,
                   help="open-loop arrival rate; omit for closed-loop throughput")
    p.add_argument("--head", default="embedding",
                   choices=("embedding", "dlrm"))
    p.add_argument("--train-steps", type=int, default=0,
                   help="warm the table with N training steps before serving")
    args = p.parse_args(argv)
    enable_compile_cache()

    if get_arch(args.arch).kind == "recsys":
        sess = Session.from_arch(
            args.arch, reduced=args.reduced, seed=args.seed,
            global_batch=args.max_batch, seq_len=8, store=args.store)
        if args.train_steps > 0:
            sess.train(steps=args.train_steps)
        report = sess.serve_embeddings(
            num_requests=args.requests, max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms, qps=args.qps, zipf_a=args.zipf_a,
            head=args.head, store=args.store, check_exact=True)
        print("[serve] summary:", json.dumps(report.summary))
        return report.results

    # LLM path: small train-shaped host workload; .serve() resolves the
    # decode-shaped workload (prompt+gen KV cache) internally.
    sess = Session.from_arch(args.arch, reduced=args.reduced, seed=args.seed,
                             global_batch=args.batch, seq_len=32)
    report = sess.serve(batch=args.batch, prompt_len=args.prompt_len,
                        gen=args.gen)
    print("[serve] summary:", json.dumps(report.summary))
    return report.tokens


if __name__ == "__main__":
    serve()
