"""REPRO_TASK=lm: personalized LM fine-tuning as plane rows.

Each simulated device personalizes a FROZEN transformer base by training a
small delta pytree of LoRA factors, applied to activations beside the
frozen weights (``y = x W + s (x a) b``, ``s = 1/r``; ``layers.lora``) and
never merged into per-client weights, so every client of a launch shares
one base matmul:

* ``head_a``/``head_b`` — LoRA on the output head's logits (the LM
  analogue of a per-client classifier head); with tied embeddings the
  same update moves each token's input row too, as a merged tied head
  would;
* one LoRA per adapter target (``targets``: ``wq``, and for latent
  attention also ``w_dkv``, the latent down-projection) on every attention
  layer: ``<target>/slot<i>`` stacked over the scanned periods, and
  ``prefix/layer<i>/<target>`` for the leading unrolled layers. Local
  training then runs attention forward AND backward, not just a probe.

Only the delta rides the wire and becomes a plane row. The task holds the
base in a :class:`FrozenBase` (a ``register_static`` pytree wrapper with
zero array leaves), so ``simulator.model_bytes`` bills uploads/downlinks at
delta size and the server's plane stores ``dim = size(delta)`` rows. The
fleet passes the base into every launch as a device operand
(:attr:`LMTask.operands`); it is never folded into a program as a constant.

The per-client arithmetic is written for a batch of clients: every delta
leaf carries a leading client axis, the clients' sequences are one
client-major batch of the forward, and the loss is the sum of the clients'
mean NLLs, so each client's gradient is its own.

Per-client data is a token stream (:mod:`repro.data.lm`): clients in the
same latent cluster share one Zipf+Markov distribution (same support
permutation and successor table) but draw disjoint sequences. Feedback
distributions (Eq. 2/3) histogram token ids into ``buckets`` classes
(``token_id % J``) — the LM analogue of the MLP label histogram, sized so
the server's chi2 kernels stay (J,)-cheap regardless of vocab.

LoRA b-factors init to zero, so every client's initial delta row sits at
the plane origin and row distance directly measures personalization
divergence — the EchoPFL Eq. 1 metric, unpolluted by base weights.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.data.lm import TokenStream, TokenStreamConfig
from repro.fl.tasks import FleetData, pad_rows
from repro.models.model import forward as model_forward
from repro.models.model import init_params as model_init_params

PyTree = Any
F32 = 4


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True, eq=False)
class FrozenBase:
    """Static pytree wrapper for the frozen base parameters.

    ``register_static`` makes it flatten to ZERO leaves (the whole object
    is treedef metadata), which is what keeps the base out of every
    leaf-walking code path at once: ``model_bytes`` bills payloads that
    carry it at 0 bytes and ``flatten_spec`` rows exclude it. ``eq=False``
    gives identity hash/eq — comparing multi-MB pytrees per jit-cache lookup
    would be absurd. Launches take ``params`` as an argument
    (:attr:`LMTask.operands`)."""

    params: PyTree


@dataclasses.dataclass
class LMClientData:
    """One client's token sequences, pre-split. Mirrors the surface the
    coordination layers read from ``ClientDataset``: ``n`` (upload
    weighting) and ``label_histogram`` (feedback f_true)."""

    tokens_train: np.ndarray  # (n_train, S) int32
    labels_train: np.ndarray  # (n_train, S) int32 next-token targets
    tokens_test: np.ndarray  # (n_test, S) int32
    labels_test: np.ndarray
    latent_cluster: int = 0

    @property
    def n(self) -> int:
        return len(self.tokens_train)

    def label_histogram(self, num_classes: int) -> np.ndarray:
        """Counts of target tokens per ``token_id % J`` bucket (the LM
        analogue of the MLP class histogram — counts, not frequencies,
        matching ``ClientDataset.label_histogram``)."""
        return np.bincount(
            self.labels_train.reshape(-1) % num_classes, minlength=num_classes
        ).astype(np.float64)


def _per_client(v: jax.Array, leaf: jax.Array) -> jax.Array:
    """A (C,) per-client value broadcast against a leaf with a leading
    client axis."""
    return v.reshape(v.shape + (1,) * (leaf.ndim - 1))


@dataclasses.dataclass(frozen=True)
class LMTask:
    """PersonalizationTask over LoRA/head deltas on a frozen base.

    Frozen + hashable by value of everything but the base (``cfg``, rank,
    targets, buckets), so the fleet's static-task jit cache keys on the
    shapes: launches take the base as an operand (:attr:`operands`), and two
    tasks over different draws of one config share their programs."""

    base: FrozenBase = dataclasses.field(compare=False)
    cfg: ModelConfig
    lora_rank: int = 4
    buckets: int = 16
    targets: tuple[str, ...] = ("wq",)
    name: str = "lm"

    @classmethod
    def build(cls, cfg: ModelConfig, seed: int, *, lora_rank: int = 4, targets: tuple[str, ...] = ("wq",),
              dtype=jnp.float32, buckets: int = 16) -> "LMTask":
        """A task over a base of ``cfg`` drawn from ``seed`` and stored as
        ``dtype``, with rank-``lora_rank`` adapters on ``targets``."""
        base = jax.jit(model_init_params, static_argnums=(0, 2))(cfg, jax.random.PRNGKey(seed), dtype)
        return cls(base=FrozenBase(base), cfg=cfg, lora_rank=lora_rank, buckets=buckets,
                   targets=tuple(targets))

    @property
    def operands(self) -> PyTree:
        """The device arrays every launch takes: the frozen base."""
        return self.base.params

    @property
    def lora_scale(self) -> float:
        return 1.0 / self.lora_rank

    # ---- delta pytree ---------------------------------------------------
    def _target_width(self, target: str) -> int:
        cfg = self.cfg
        if target == "wq":
            qk = cfg.resolved_head_dim if cfg.mla is None else cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
            return cfg.num_heads * qk
        if target == "w_dkv" and cfg.mla is not None:
            return cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        raise ValueError(f"no adapter target {target!r} in {cfg.name}")

    def init_params(self, key: jax.Array) -> PyTree:
        cfg, r = self.cfg, self.lora_rank
        d, V, P = cfg.d_model, cfg.padded_vocab, cfg.num_periods
        k_head, k_ad = jax.random.split(key)
        delta: dict[str, Any] = {
            # standard LoRA init: a random, b zero — the initial delta is an
            # exact zero update, so initial rows sit at the plane origin
            "head_a": jax.random.normal(k_head, (d, r), jnp.float32) / np.sqrt(d),
            "head_b": jnp.zeros((r, V), jnp.float32),
        }

        def factors(k, width, lead=()):
            return {"a": jax.random.normal(k, (*lead, d, r), jnp.float32) / np.sqrt(d),
                    "b": jnp.zeros((*lead, r, width), jnp.float32)}

        prefix = {}
        for i, spec in enumerate(cfg.prefix):
            if spec.mixer in ("attn", "attn_local"):
                layer = {}
                for t in self.targets:
                    k_ad, k = jax.random.split(k_ad)
                    layer[t] = factors(k, self._target_width(t))
                prefix[f"layer{i}"] = layer
        if prefix:
            delta["prefix"] = prefix
        for t in self.targets:
            delta[t] = {}
            for i, spec in enumerate(cfg.pattern):
                if spec.mixer in ("attn", "attn_local"):
                    k_ad, k = jax.random.split(k_ad)
                    delta[t][f"slot{i}"] = factors(k, self._target_width(t), (P,))
        return delta

    def _adapters(self, delta: PyTree) -> PyTree:
        """The forward's adapter pytree for a batch of deltas (leading client
        axis): scanned slots' factors get the period axis first."""
        cfg = self.cfg
        ad: dict[str, Any] = {"head": {"a": delta["head_a"], "b": delta["head_b"]}}
        if cfg.prefix:
            prefix = delta.get("prefix", {})
            ad["prefix"] = [prefix.get(f"layer{i}") for i in range(len(cfg.prefix))]
        blocks: dict[str, Any] = {}
        for t in self.targets:
            for slot, ab in delta[t].items():
                blocks.setdefault(slot, {})[t] = {k: jnp.swapaxes(v, 0, 1) for k, v in ab.items()}
        ad["blocks"] = blocks
        return ad

    # ---- per-client arithmetic, batched over clients -------------------
    def _forward(self, base, delta, tokens):
        """Logits (C, n, S, V) float32 and the expert layers' counts
        (``model.forward``'s) for the clients' sequences ``tokens``
        (C, n, S), each position's count of pairs routed to held experts
        as (C, n, S)."""
        C, n, S = tokens.shape
        embeds = jnp.take(base["embed"], tokens, axis=0).astype(jnp.float32)  # (C, n, S, d)
        if self.cfg.tie_embeddings:
            # the tied head's update is the embedding's too: each token's
            # input row moves by s * a @ b[:, token]
            b_tok = jax.vmap(lambda b, t: b[:, t])(delta["head_b"], tokens)  # (C, r, n, S)
            embeds = embeds + self.lora_scale * jnp.einsum("crns,cdr->cnsd", b_tok, delta["head_a"])
        embeds = embeds.reshape(C * n, S, -1)
        logits, _, _, routing = model_forward(
            self.cfg, base, {"embeds": embeds}, adapters=self._adapters(delta),
            lora_scale=self.lora_scale, routed=True,
        )
        return (logits.astype(jnp.float32).reshape(C, n, S, -1),
                {**routing, "pairs": routing["pairs"].reshape(C, n, S)})

    def _nll(self, base, delta, tokens, labels, seq_mask):
        """Per-client mean next-token NLL over valid sequences (padded rows
        masked), and the expert layers' counts with ``pairs`` per client."""
        logits, routing = self._forward(base, delta, tokens)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        per = (lse - picked) * seq_mask[..., None]  # (C, n, S)
        denom = jnp.maximum(jnp.sum(seq_mask, axis=1) * tokens.shape[2], 1.0)
        pairs = jnp.sum(routing["pairs"] * seq_mask[..., None].astype(jnp.int32), axis=(1, 2))
        return jnp.sum(per, axis=(1, 2)) / denom, {**routing, "pairs": pairs}

    def _scan_train(self, base, delta, tokens, labels, seq_mask, lr, epochs, head_frac,
                    max_epochs: int):
        """Multi-epoch full-batch SGD on the clients' deltas, mirroring
        ``mlp._scan_train``: steps past a client's ``epochs`` budget carry
        it through untouched, and head-only fine-tuning selects the
        attention LoRA gradients to exact zeros (the head LoRA is the LM
        analogue of the MLP's last layer). Returns the deltas, the last
        active epoch's losses and the expert layers' counts over the forward
        passes of the active epochs: ``pairs`` routed to held experts per
        client, and the launch's layer ``calls`` in epochs where any client
        is active, those of them that ran the ``small``-est pair buffer and
        the buffers' ``rows``."""

        def loss_fn(p):
            nll, routing = self._nll(base, p, tokens, labels, seq_mask)
            return jnp.sum(nll), (nll, routing)

        def step(carry, e):
            p, last_loss, counts = carry
            (_, (loss, routing)), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            freeze_body = head_frac > 0
            grads = {k: g if k in ("head_a", "head_b") else jax.tree_util.tree_map(
                lambda x: jnp.where(_per_client(freeze_body, x), jnp.zeros_like(x), x), g)
                for k, g in grads.items()}
            active = e < epochs
            p2 = jax.tree_util.tree_map(
                lambda a, g: jnp.where(_per_client(active, a), a - _per_client(lr, a) * g, a), p, grads)
            counts = {k: v + jnp.where(active if k == "pairs" else jnp.any(active), routing[k], 0)
                      for k, v in counts.items()}
            return (p2, jnp.where(active, loss, last_loss), counts), None

        C = tokens.shape[0]
        zero = jnp.zeros((), jnp.int32)
        counts = {"pairs": jnp.zeros((C,), jnp.int32), "calls": zero, "small": zero, "rows": zero}
        (delta, loss, counts), _ = jax.lax.scan(step, (delta, jnp.zeros((C,), jnp.float32), counts),
                                                jnp.arange(max_epochs))
        return delta, loss, counts

    def _accuracy(self, base, delta, tokens, labels, seq_mask):
        logits, _ = self._forward(base, delta, tokens)
        correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32) * seq_mask[..., None]
        denom = jnp.maximum(jnp.sum(seq_mask, axis=1) * tokens.shape[2], 1.0)
        return jnp.sum(correct, axis=(1, 2)) / denom

    def _distributions(self, base, delta, tokens, seq_mask, num_classes: int):
        """(F_pred, S_soft) per client over ``token_id % J`` buckets:
        predicted-token bucket counts and the mean bucket-aggregated
        softmax."""
        J = num_classes
        logits, _ = self._forward(base, delta, tokens)  # (C, n, S, V)
        probs = jax.nn.softmax(logits, axis=-1)
        pred = jnp.argmax(logits, axis=-1)
        bucket = jax.nn.one_hot(jnp.arange(logits.shape[-1]) % J, J)  # (V, J)
        valid = seq_mask[..., None]  # (C, n, 1)
        hist = jnp.sum(jax.nn.one_hot(pred % J, J) * valid[..., None], axis=(1, 2))
        sprob = jnp.einsum("cnsv,vj->cnsj", probs, bucket) * valid[..., None]
        denom = jnp.maximum(jnp.sum(seq_mask, axis=1) * tokens.shape[2], 1.0)
        return hist, jnp.sum(sprob, axis=(1, 2)) / denom[:, None]

    # ---- fleet engine (batched; called inside the fleet's jits) ---------
    def build_fleet_data(self, datasets, shard, num_classes):
        n_tr = max(d.n for d in datasets)
        n_te = max(len(d.tokens_test) for d in datasets)

        def stack(attr, n):
            return shard(jnp.asarray(np.stack(
                [pad_rows(np.asarray(getattr(d, attr), np.int32), n) for d in datasets]
            )))

        def masks(n, lens):
            return shard(jnp.asarray(np.stack(
                [pad_rows(np.ones(k, np.float32), n) for k in lens]
            )))

        train = {
            "tokens": stack("tokens_train", n_tr),
            "labels": stack("labels_train", n_tr),
            "mask": masks(n_tr, [d.n for d in datasets]),
        }
        test = {
            "tokens": stack("tokens_test", n_te),
            "labels": stack("labels_test", n_te),
            "mask": masks(n_te, [len(d.tokens_test) for d in datasets]),
        }
        f_true = np.stack([
            d.label_histogram(num_classes).astype(np.float32) for d in datasets
        ])
        tokens = np.asarray([d.tokens_train.size for d in datasets], np.int64)
        return FleetData(train=train, test=test, f_true=f_true, tokens=tokens)

    def client_bytes(self, kind: str, data: dict) -> int:
        """Device bytes one client's share of a ``kind`` launch (``train``,
        ``eval``, ``feedback``) holds at its peak, from the shapes alone, in
        float32 values per token: for training, what every layer keeps for
        the backward pass (two residual-stream rows and two rows of its FFN
        hidden width: dense, or the shared experts and every pair the token
        may route to held experts) and the logits with their softmax and
        gradient; for a forward pass, the widest layer's and the logits."""
        cfg = self.cfg
        per_layer = []
        for spec in cfg.all_layers:
            hidden = 0
            if spec.ffn == "dense":
                hidden = cfg.d_ff
            elif spec.ffn == "moe":
                hidden = (cfg.moe.num_shared + min(cfg.moe.top_k, cfg.moe.num_held)) * cfg.moe.d_expert
            per_layer.append(2 * cfg.d_model + 2 * hidden)
        if kind == "train":
            per_token = sum(per_layer) + 3 * cfg.padded_vocab
        else:
            per_token = max(per_layer) + cfg.padded_vocab
        return F32 * per_token * int(np.prod(data["tokens"].shape[1:]))

    def fleet_local_train(self, ops, params_b, train, lr, epochs, head, *, max_epochs):
        delta, loss, counts = self._scan_train(
            ops, params_b, train["tokens"], train["labels"], train["mask"], lr, epochs, head,
            max_epochs=max_epochs)
        # the launch-wide counts ride the first client's row: every block
        # of the launch and the fleet's cut to its real clients keep it
        first = jnp.arange(loss.shape[0]) == 0
        return delta, loss, {"routed": counts.pop("pairs"),
                             **{k: jnp.where(first, v, 0) for k, v in counts.items()}}

    def fleet_evaluate(self, ops, params_b, test):
        return self._accuracy(ops, params_b, test["tokens"], test["labels"], test["mask"])

    def fleet_feedback(self, ops, params_b, train, num_classes):
        return self._distributions(ops, params_b, train["tokens"], train["mask"], num_classes)

    # ---- per-client entry points (loop backend / SimClient) -------------
    def local_train(self, params, data, *, epochs, lr, head_only):
        one = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], params)
        delta, loss = _client_train(
            self, self.operands, one, jnp.asarray(data.tokens_train)[None],
            jnp.asarray(data.labels_train)[None], jnp.ones((1, data.n), jnp.float32),
            jnp.full((1,), lr, jnp.float32), jnp.full((1,), epochs, jnp.int32),
            jnp.full((1,), 1.0 if head_only else 0.0, jnp.float32), max_epochs=epochs,
        )
        return jax.tree_util.tree_map(lambda x: x[0], delta), loss[0]

    def evaluate(self, params, data):
        one = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], params)
        return float(_client_eval(
            self, self.operands, one, jnp.asarray(data.tokens_test)[None],
            jnp.asarray(data.labels_test)[None], jnp.ones((1, len(data.tokens_test)), jnp.float32),
        )[0])

    def feedback_inputs(self, params, data, num_classes):
        one = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], params)
        f_pred, s_soft = _client_feedback(
            self, self.operands, one, jnp.asarray(data.tokens_train)[None],
            jnp.ones((1, data.n), jnp.float32), num_classes=num_classes,
        )
        f_true = data.label_histogram(num_classes)
        return np.asarray(f_pred[0]), f_true.astype(np.float32), np.asarray(s_soft[0])


@functools.partial(jax.jit, static_argnames=("task", "max_epochs"))
def _client_train(task, base, delta, tokens, labels, mask, lr, epochs, head_frac, *,
                  max_epochs: int):
    delta, loss, _ = task._scan_train(base, delta, tokens, labels, mask, lr, epochs, head_frac,
                                      max_epochs=max_epochs)
    return delta, loss


@functools.partial(jax.jit, static_argnames=("task",))
def _client_eval(task, base, delta, tokens, labels, mask):
    return task._accuracy(base, delta, tokens, labels, mask)


@functools.partial(jax.jit, static_argnames=("task", "num_classes"))
def _client_feedback(task, base, delta, tokens, mask, *, num_classes: int):
    return task._distributions(base, delta, tokens, mask, num_classes)


# ---------------------------------------------------------------------------
# data + experiment drivers
# ---------------------------------------------------------------------------


_DEFAULT_LM_TASK: LMTask | None = None


def default_lm_task() -> LMTask:
    """The singleton ``REPRO_TASK=lm`` task (tiny_lm base, PRNGKey(0)).

    A singleton on purpose: the task is a static jit-cache key, so every
    resolver call must hand back the SAME object or each lookup would
    recompile the fleet launches."""
    global _DEFAULT_LM_TASK
    if _DEFAULT_LM_TASK is None:
        _DEFAULT_LM_TASK = LMTask.build(get_config("tiny_lm"), 0)
    return _DEFAULT_LM_TASK


def make_lm_data(
    num_clients: int,
    *,
    vocab_size: int,
    latent_clusters: int = 4,
    n_train: int = 8,
    n_test: int = 4,
    seq_len: int = 32,
    seed: int = 0,
) -> list[LMClientData]:
    """Per-client token datasets with cluster-structured heterogeneity.

    All clients of a latent cluster share one stream DISTRIBUTION (support
    permutation + Markov successor table come from the cluster seed); each
    client then draws its own sequences from a reseeded sampler — same
    personalization geometry as the synthetic MLP tasks."""
    out = []
    for i in range(num_clients):
        cl = i % latent_clusters
        stream = TokenStream(TokenStreamConfig(
            vocab_size=vocab_size, seq_len=seq_len, batch_size=1,
            seed=7000 + 17 * cl + seed,
        ))
        # distribution tables are built; re-seed only the sampling rng
        stream.rng = np.random.default_rng(100_003 * (seed + 1) + i)
        seqs = np.stack([stream._sample_seq(seq_len + 1) for _ in range(n_train + n_test)])
        tok, lab = seqs[:, :-1].astype(np.int32), seqs[:, 1:].astype(np.int32)
        out.append(LMClientData(
            tokens_train=tok[:n_train], labels_train=lab[:n_train],
            tokens_test=tok[n_train:], labels_test=lab[n_train:],
            latent_cluster=cl,
        ))
    return out


def build_lm_clients(
    num_clients: int,
    *,
    seed: int = 0,
    latent_clusters: int = 4,
    device_mix: dict | None = None,
    base_round_time: float = 30.0,
    local_epochs: int = 2,
    lr: float = 0.5,
    n_train: int = 8,
    n_test: int = 4,
    seq_len: int = 32,
    task: LMTask | None = None,
):
    """(clients, task, init_delta) for the LM workload — the LM analogue of
    ``experiment.build_clients``."""
    from repro.core.client import SimClient
    from repro.fl.devices import PAPER_SIM_MIX, make_device_fleet

    task = task or default_lm_task()
    rng = np.random.default_rng(seed)
    datasets = make_lm_data(
        num_clients, vocab_size=task.cfg.vocab_size, latent_clusters=latent_clusters,
        n_train=n_train, n_test=n_test, seq_len=seq_len, seed=seed,
    )
    fleet = make_device_fleet(num_clients, rng, device_mix or PAPER_SIM_MIX, base_round_time)
    clients = [
        SimClient(
            client_id=i,
            data=datasets[i],
            num_classes=task.buckets,
            device_class=fleet[i]["class"],
            round_time_fn=fleet[i]["round_time"],
            local_epochs=local_epochs,
            lr=lr,
            task=task,
        )
        for i in range(num_clients)
    ]
    init_delta = task.init_params(jax.random.PRNGKey(seed))
    return clients, task, init_delta


def run_lm_experiment(
    strategy_name: str,
    *,
    num_clients: int = 8,
    seed: int = 0,
    max_time: float = 1800.0,
    rounds: int = 5,
    eval_interval: float = 120.0,
    network=None,
    local_epochs: int = 2,
    base_round_time: float = 30.0,
    client_backend: str | None = None,
    uplink=None,
    latent_clusters: int = 4,
    n_train: int = 8,
    n_test: int = 4,
    seq_len: int = 32,
    **strategy_kw,
):
    """End-to-end LM personalization run: returns (task, clients, strategy,
    report) like ``experiment.run_experiment``. Sync strategies go through
    ``run_sync`` round barriers; async ones through the (coalesced) event
    loop — both on delta payloads."""
    from repro.fl.experiment import build_strategy
    from repro.fl.network import NetworkModel
    from repro.fl.simulator import Simulator

    clients, task, init_delta = build_lm_clients(
        num_clients, seed=seed, latent_clusters=latent_clusters,
        base_round_time=base_round_time, local_epochs=local_epochs,
        n_train=n_train, n_test=n_test, seq_len=seq_len,
    )
    strategy = build_strategy(strategy_name, init_delta, clients, seed=seed, **strategy_kw)
    sim = Simulator(
        clients, strategy,
        network=network or NetworkModel(),
        eval_interval=eval_interval, seed=seed, client_backend=client_backend,
        uplink=uplink,
    )
    report = sim.run(max_time=max_time, rounds=rounds)
    report.extra["task"] = "lm"
    report.extra["latent_clusters"] = {c.client_id: c.data.latent_cluster for c in clients}
    return task, clients, strategy, report
