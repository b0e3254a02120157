"""Training launcher: thin CLI over ``repro.api.Session``.

End-to-end NestPipe training with checkpoint/restart, watchdog straggler
detection, and preemption-safe saves — all owned by the Session; this module
only parses flags. CPU-scale entry point (reduced configs run real steps
here; the production mesh path is exercised by the dry-run):

    python -m repro.launch.train --arch hstu-industrial --reduced \
        --steps 200 --mode nestpipe --ckpt-dir /tmp/ck --ckpt-every 50
"""
from __future__ import annotations

import argparse
import json
import signal

from ..api import Session, available_strategies
from ..core.store import STORES
from .compile_cache import enable_compile_cache


def train(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", default="train_4k")
    p.add_argument("--mode", default="nestpipe", choices=available_strategies())
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--n-micro", type=int, default=4)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--global-batch", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--store", default="auto", choices=("auto", *STORES),
                   help="embedding storage tier (core/store; auto = "
                        "$REPRO_STORE then device)")
    p.add_argument("--prefetch-ahead", type=int, default=1,
                   help="DBP retrieval lookahead depth k")
    args = p.parse_args(argv)
    enable_compile_cache()

    # CPU-scale run: no mesh (single device); the production-mesh config is
    # proven by the dry-run.
    sess = Session.from_arch(
        args.arch, mode=args.mode, reduced=args.reduced, shape=args.shape,
        global_batch=args.global_batch, seq_len=args.seq_len,
        n_micro=args.n_micro, lr=args.lr, seed=args.seed,
        store=args.store, prefetch_ahead=args.prefetch_ahead,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        preemption_signals=(signal.SIGTERM,),
    )
    if args.resume and args.ckpt_dir:
        last = sess.restore_if_available()
        if last is not None:
            print(f"[train] resumed from step {int(sess.state.step)}")

    remaining = args.steps - int(sess.state.step)
    report = sess.train(max(remaining, 0))
    print("[train] summary:", json.dumps(report.summary))
    return report.state, report.stats


if __name__ == "__main__":
    train()
