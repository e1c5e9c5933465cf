"""Batched serving example: continuous batching over a static window.

Eight requests share four decode slots; retired slots admit queued requests.

    PYTHONPATH=src python examples/serve_batched.py [--arch rwkv6_3b]
"""

import argparse

import numpy as np
import jax

import repro.configs as C
from repro.models import model as M
from repro.serve.decode import ServeConfig, Server, greedy_decode
from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()
    use_compile_cache()

    cfg = C.get_smoke(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    # one-shot batched greedy decode
    prompt = rng.integers(2, cfg.vocab, size=(2, 5)).astype(np.int32)
    toks = greedy_decode(params, cfg, prompt, max_new=8, cache_len=64)
    print("greedy_decode:", np.asarray(toks).tolist())

    # continuous batching server
    server = Server(params, cfg, ServeConfig(batch=4, cache_len=128, max_new=args.max_new))
    rids = [
        server.submit(rng.integers(2, cfg.vocab, size=int(rng.integers(2, 6))).tolist())
        for _ in range(args.requests)
    ]
    server.run(n_steps=args.requests * (args.max_new + 8))
    done = sum(1 for r in rids if r in server.done)
    print(f"completed {done}/{len(rids)} requests")
    for rid in rids[:4]:
        print(f"  request {rid}: {server.done.get(rid, 'PENDING')}")


if __name__ == "__main__":
    main()
