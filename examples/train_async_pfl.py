"""End-to-end driver: federated training of transformer clients with the
EchoPFL protocol + fault tolerance.

Each federated client is a reduced llama3.2-1b-family transformer (the same
config family as the production 1B model, scaled to CPU) training a causal
LM on its own synthetic token distribution. The EchoPFL server clusters the
clients by parameter distance, aggregates asynchronously, broadcasts on
demand, and checkpoints its full state (cluster centers, RNN predictor,
Top-K records) — the run can be killed and resumed.

    PYTHONPATH=src python examples/train_async_pfl.py [--steps 300] [--resume]
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer, restore_pytree, save_pytree
from repro.common.compile_cache import use_compile_cache
from repro.configs import ARCH_REGISTRY
from repro.configs.base import reduced_config
from repro.core.server import EchoPFLServer
from repro.data.lm import token_stream
from repro.models import init_params, make_train_step
from repro.models.steps import TrainState, make_optimizer

CKPT_DIR = "experiments/train_async_pfl_ckpt"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced_config(ARCH_REGISTRY["llama3.2-1b"], d_model=64, periods=2)
    key = jax.random.PRNGKey(0)
    init = init_params(cfg, key)
    opt = make_optimizer(cfg)
    train_step = jax.jit(make_train_step(cfg))

    # two latent "user groups" with different token distributions
    streams = [token_stream(cfg.vocab_size, seed=i % 2, batch=4, seq=32) for i in range(args.clients)]
    states = [TrainState(init, opt.init(init), jnp.zeros((), jnp.int32)) for _ in range(args.clients)]

    server = EchoPFLServer(init, num_initial_clusters=2, seed=0)
    ck = Checkpointer(CKPT_DIR, keep=2)
    start = 0
    if args.resume:
        from repro.checkpoint.checkpointer import latest_step

        step = latest_step(CKPT_DIR)
        if step is not None:
            d = os.path.join(CKPT_DIR, f"step_{step:010d}")
            _, extra = restore_pytree(d, like=None)  # manifest first: meta drives template
            template = {"server": server.state_template(extra["server_meta"])}
            tree, extra = restore_pytree(d, like=template)
            server.load_state(tree["server"], extra["server_meta"])
            start = step
            print(f"resumed server state at round {start}")

    t0 = time.time()
    losses = {i: [] for i in range(args.clients)}
    rng = np.random.default_rng(0)
    for rnd in range(start, args.steps):
        cid = int(rng.integers(args.clients))  # async: clients arrive in random order
        base = server.model_for(cid)
        st = states[cid]._replace(params=base)
        loss = None
        for _ in range(args.local_steps):
            st, metrics = train_step(st, next(streams[cid]))
            loss = float(metrics["loss"])
        states[cid] = st
        losses[cid].append(loss)
        downlinks = server.handle_upload(cid, st.params, 0, 128, t=time.time() - t0)
        for dl in downlinks:  # apply fresh models (unicast + broadcasts)
            states[dl.client_id] = states[dl.client_id]._replace(params=dl.params)
        if (rnd + 1) % 50 == 0:
            tree, meta = server.state_dict()
            ck.save(rnd + 1, {"server": tree}, extra={"server_meta": meta})
            mean_loss = np.mean([l[-1] for l in losses.values() if l])
            print(f"round {rnd+1:4d}: loss={mean_loss:.4f} "
                  f"clusters={server.stats()['clusters']} "
                  f"broadcasts={server.stats()['broadcasts']}")

    print("\n-- final --")
    first = {i: losses[i][0] for i in losses if losses[i]}
    last = {i: losses[i][-1] for i in losses if losses[i]}
    for i in sorted(first):
        print(f"client {i}: first_loss={first[i]:.4f} last_loss={last[i]:.4f}")
    assert all(last[i] < first[i] for i in last), "every client's LM loss must improve"
    a = server.clustering.assignment
    same_group = [a.get(i) for i in range(args.clients)]
    print(f"cluster assignment: {same_group} (clients with even/odd ids share token stats)")
    ck.close()


if __name__ == "__main__":
    main()
