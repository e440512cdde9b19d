"""Serving example: batched decode from per-cluster personalized models.

After an EchoPFL run the server holds one model per cluster ("branches" in
the CI scheme). This example serves batched generation requests against the
right personalized model for each requester, using the fixed-size KV-cache
decode path (the same serve_step the dry-run lowers for decode_32k).

    PYTHONPATH=src python examples/serve_cluster_models.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import use_compile_cache
from repro.configs import ARCH_REGISTRY
from repro.configs.base import reduced_config
from repro.core.server import EchoPFLServer
from repro.data.lm import token_stream
from repro.models import init_cache, init_params, make_serve_step, make_train_step
from repro.models.steps import TrainState, make_optimizer, make_prefill_step


def main() -> None:
    use_compile_cache()
    cfg = reduced_config(ARCH_REGISTRY["gemma2-2b"], d_model=64, periods=2)
    key = jax.random.PRNGKey(0)
    init = init_params(cfg, key)
    opt = make_optimizer(cfg)
    train = jax.jit(make_train_step(cfg))

    # --- quick federated phase: 4 clients, 2 latent token distributions ---
    server = EchoPFLServer(init, num_initial_clusters=2, seed=0)
    streams = [token_stream(cfg.vocab_size, seed=i % 2) for i in range(4)]
    states = [TrainState(init, opt.init(init), jnp.zeros((), jnp.int32)) for _ in range(4)]
    for rnd in range(40):
        cid = rnd % 4
        st = states[cid]._replace(params=server.model_for(cid))
        for _ in range(3):
            st, _ = train(st, next(streams[cid]))
        states[cid] = st
        server.handle_upload(cid, st.params, 0, 128, t=float(rnd))
    print(f"federated phase done: {server.stats()['clusters']} personalized clusters")

    # --- serving phase: requests routed to their cluster's model ----------
    prefill = jax.jit(make_prefill_step(cfg))
    serve = jax.jit(make_serve_step(cfg), donate_argnums=1)

    requests = [
        {"client": 0, "prompt_len": 8, "gen": 16},
        {"client": 1, "prompt_len": 8, "gen": 16},
        {"client": 2, "prompt_len": 8, "gen": 16},
        {"client": 3, "prompt_len": 8, "gen": 16},
    ]
    # batch requests per cluster (one decode batch per personalized model)
    by_cluster: dict[int, list[dict]] = {}
    for r in requests:
        by_cluster.setdefault(server.clustering.assignment[r["client"]], []).append(r)

    rng = np.random.default_rng(0)
    for cluster_id, batch_reqs in sorted(by_cluster.items()):
        params = server.clustering.clusters[cluster_id].center
        B = len(batch_reqs)
        L = batch_reqs[0]["prompt_len"]
        gen = batch_reqs[0]["gen"]
        prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, L)))

        t0 = time.time()
        logits, pre_cache = prefill(params, {"tokens": prompts})
        # graft prefill cache into a fixed-size buffer with generation margin
        cache = init_cache(cfg, B, ctx_len=L, margin=gen + 8)
        def graft(fixed, pre):
            if fixed.shape == pre.shape:
                return pre
            axis = next(i for i, (a, b) in enumerate(zip(fixed.shape, pre.shape)) if a != b)
            pad = [(0, 0)] * fixed.ndim
            pad[axis] = (0, fixed.shape[axis] - pre.shape[axis])
            return jnp.pad(pre, pad)
        cache = jax.tree_util.tree_map(graft, cache, pre_cache)

        out_tokens = []
        tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None]
        for _ in range(gen):
            out_tokens.append(np.asarray(tok))
            logits, cache = serve(params, cache, {"tokens": tok})
            tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None]
        dt = time.time() - t0
        toks = np.concatenate(out_tokens, axis=1)
        print(f"cluster {cluster_id}: served {B} reqs x {gen} tokens "
              f"in {dt:.2f}s ({B * gen / dt:.0f} tok/s) "
              f"sample={toks[0, :8].tolist()}")
    print("OK")


if __name__ == "__main__":
    main()
