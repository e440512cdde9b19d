"""Client model: LoRA deltas over a frozen MLA + MoE base (DeepSeek-V2's
architecture), one chip's share of an expert-parallel deployment.

A configuration names it with ``"client_model": "mla_moe_lora"`` and gives
the published config's keys (``hidden_size``, ``kv_lora_rank``,
``n_routed_experts``, ...) beside the cut: ``num_periods`` expert layers
after the leading dense ones, ``experts_held`` experts of each from
``experts_held_first`` on, and the first ``vocab_held`` ids of the
vocabulary. ``program_config`` names the program's registered config that
the published keys must match (``check_sizes``); the program runs that
config with the cut applied (:func:`program_config`), through the
program's ``LMTask``. A plane row is the flattened delta: rank
``lora_rank`` LoRA on the head and on ``lora_targets`` of every attention
layer.

Clients hold ``make_lm_data`` token streams over the vocabulary slice. A
seed relabels the slice by a permutation within residue classes mod the
feedback buckets, applied alike to the data, the embedding rows, the head's
columns and ``head_b``'s: the same computation under other names, so every
seed meets the same shapes and routes. The counts below are the
yardstick's own, from the configuration's sizes alone.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from chipbench import spec

BENCH_DIR = Path(__file__).resolve().parents[1]
BF16 = 2


# ------------------------------------------------------------------ sizes
def _sizes(config: dict) -> dict:
    """The configuration's sizes as the reference takes them."""
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "qk_nope": config["qk_nope_head_dim"], "qk_rope": config["qk_rope_head_dim"],
        "v_head": config["v_head_dim"], "kv_lora": config["kv_lora_rank"],
        "experts": config["n_routed_experts"], "top_k": config["num_experts_per_tok"],
        "held_first": config["experts_held_first"], "experts_held": config["experts_held"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "rope_theta": float(config["rope_theta"]), "rope_scaling": config["rope_scaling"],
        "eps": config["rms_norm_eps"], "lora_rank": config["lora_rank"],
    }


def _published(cfg) -> dict:
    """The published keys of a program config (``ModelConfig``)."""
    moe, mla, y = cfg.moe, cfg.mla, cfg.rope_scaling
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": len(cfg.prefix), "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "tie_word_embeddings": cfg.tie_embeddings,
        "moe_intermediate_size": moe.d_expert, "n_routed_experts": moe.num_experts,
        "num_experts_per_tok": moe.top_k, "n_shared_experts": moe.num_shared,
        "norm_topk_prob": moe.norm_topk_prob, "routed_scaling_factor": moe.routed_scaling_factor,
        "kv_lora_rank": mla.kv_lora_rank, "qk_nope_head_dim": mla.qk_nope_head_dim,
        "qk_rope_head_dim": mla.qk_rope_head_dim, "v_head_dim": mla.v_head_dim,
        "q_lora_rank": mla.q_lora_rank,
        "rope_scaling": {"type": "yarn", "factor": y.factor, "original_max_position_embeddings":
                         y.original_max_position, "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
    }


def check_sizes(config: dict) -> None:
    """Raises where the configuration's published sizes are not those of the
    program's ``program_config``, or a program lacks what the cut needs."""
    from repro.configs import get_config

    cfg = get_config(config["program_config"])
    try:
        program = _published(cfg)
    except AttributeError as e:  # a program without the held share, the gate's or YaRN's fields
        raise ValueError(f"the program's configs cannot state this model: {e}") from None
    mine = {k: config[k] for k in program}
    if mine != program:
        diff = {k: (mine[k], program[k]) for k in program if mine[k] != program[k]}
        raise ValueError(f"config sizes differ from the program's {config['program_config']}: {diff}")
    if config["vocab_held"] % config["buckets"] or config["vocab_held"] > cfg.vocab_size:
        raise ValueError("the vocabulary slice must be a whole number of bucket classes of the vocabulary")


def program_config(config: dict):
    """The registered config cut as the configuration says: ``num_periods``
    expert layers, the held experts (dropless), the vocabulary slice."""
    from repro.configs import get_config

    cfg = get_config(config["program_config"])
    moe = dataclasses.replace(cfg.moe, held_first=config["experts_held_first"], held=config["experts_held"])
    return dataclasses.replace(cfg, num_periods=config["num_periods"], vocab_size=config["vocab_held"],
                               moe=moe, moe_dropless=True)


# ------------------------------------------------------------------- draw
def _relabel(V: int, buckets: int, seed: int) -> np.ndarray:
    """A permutation of ids 0..V-1 within residue classes mod ``buckets``."""
    rng = np.random.default_rng([seed, 7])
    perm = np.arange(V)
    for c in range(buckets):
        ids = np.arange(c, V, buckets)
        perm[ids] = rng.permutation(ids)
    return perm


_TASK: dict = {}  # the one task of this process: {key: (task, base in the reference's layout)}


def _task(config: dict, program_seed: int, seed: int):
    """The program's task over the base drawn from ``program_seed``, with the
    base's vocabulary relabelled by ``seed``; kept for the process, so that
    both passes of a run meet the same compiled launches and the check
    retrains on the same base."""
    import json

    key = (json.dumps(config, sort_keys=True), program_seed, seed)
    if key not in _TASK:
        _TASK.clear()
        base = draw_base(config, program_seed)
        inv = np.argsort(_relabel(config["vocab_held"], config["buckets"], seed))
        base["embed"], base["lm_head"] = base["embed"][inv], base["lm_head"][:, inv]
        _TASK[key] = (_program_task(config, base), base)
    return _TASK[key][0]


def draw_base(config: dict, program_seed: int) -> dict:
    """The frozen base, random from ``program_seed``, in the reference's
    layout and as the configuration stores it (bf16 NumPy arrays): the
    embedding, the final norm's weight and the head; per layer the two
    norms' weights, MLA's 2-D projections and latent norm, and the FFN
    (dense SwiGLU in the leading dense layers; else the router over all
    ``n_routed_experts``, the held experts' SwiGLUs stacked in id order from
    ``experts_held_first``, and the shared experts as one SwiGLU of
    ``n_shared_experts`` times the expert width). Projections are normal
    over the square root of their fan-in, norm weights uniform in
    [0.75, 1.25]."""
    import ml_dtypes

    rng = np.random.default_rng([program_seed, 11])
    d, V = config["hidden_size"], config["vocab_held"]
    H, nope, rope, dv, kvr = (config[k] for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                                  "v_head_dim", "kv_lora_rank"))
    f, held = config["moe_intermediate_size"], config["experts_held"]

    def dense(*shape):
        fan_in = shape[-2]
        return (rng.standard_normal(shape, np.float32) / np.float32(np.sqrt(fan_in))).astype(ml_dtypes.bfloat16)

    def norm(n):
        return rng.uniform(0.75, 1.25, n).astype(np.float32).astype(ml_dtypes.bfloat16)

    def swiglu(width, *lead):
        return {"wg": dense(*lead, d, width), "wu": dense(*lead, d, width), "wd": dense(*lead, width, d)}

    layers = []
    for i in range(_layers(config)):
        attn = {"wq": dense(d, H * (nope + rope)), "w_dkv": dense(d, kvr + rope), "w_ukv": dense(kvr, H * (nope + dv)),
                "wo": dense(H * dv, d), "kv_norm": norm(kvr)}
        if i < config["first_k_dense_replace"]:
            ffn = swiglu(config["intermediate_size"])
        else:
            ffn = {"router": dense(d, config["n_routed_experts"]), **swiglu(f, held),
                   "shared": swiglu(config["n_shared_experts"] * f)}
        layers.append({"norm1": norm(d), "norm2": norm(d), "attn": attn, "ffn": ffn})
    return {"embed": dense(V, d), "final_norm": norm(d), "lm_head": dense(d, V), "layers": layers}


def _program_task(config: dict, base: dict):
    """The program's ``LMTask`` over ``base`` (the reference's layout), put
    in the program's parameter layout: norms as ``scale`` = weight - 1
    (exact in bf16 for weights in [0.5, 2]), MLA's projections split by
    head, the leading dense layers unrolled and the expert layers stacked
    over periods."""
    import jax

    from repro.fl.lm_task import FrozenBase, LMTask

    cfg = program_config(config)
    H = config["num_attention_heads"]

    def scale(w):
        return {"scale": (w.astype(np.float32) - 1).astype(w.dtype)}

    def layer(lp):
        a = lp["attn"]
        mixer = {"wq": a["wq"].reshape(a["wq"].shape[0], H, -1), "w_dkv": a["w_dkv"],
                 "w_ukv": a["w_ukv"].reshape(a["w_ukv"].shape[0], H, -1),
                 "wo": a["wo"].reshape(H, -1, a["wo"].shape[-1]), "kv_norm": scale(a["kv_norm"])}
        return {"norm1": scale(lp["norm1"]), "mixer": mixer, "norm2": scale(lp["norm2"]), "ffn": lp["ffn"]}

    k0 = config["first_k_dense_replace"]
    unrolled = [layer(lp) for lp in base["layers"]]
    params = {"embed": base["embed"], "final_norm": scale(base["final_norm"]), "lm_head": base["lm_head"],
              "prefix": unrolled[:k0],
              "blocks": {"slot0": jax.tree_util.tree_map(lambda *a: np.stack(a), *unrolled[k0:])}}
    params = jax.block_until_ready(jax.device_put(params))
    return LMTask(base=FrozenBase(params), cfg=cfg, lora_rank=config["lora_rank"], buckets=config["buckets"],
                  targets=tuple(config["lora_targets"]))


def draw(config: dict, rng, program_seed: int, seed: int):
    """The clients' token streams from ``rng`` and the initial delta from
    ``program_seed``, both relabelled by ``seed``; and what each client
    takes besides."""
    import jax

    from repro.fl.lm_task import LMClientData, make_lm_data

    check_sizes(config)
    task = _task(config, program_seed, seed)
    data = make_lm_data(config["num_clients"], vocab_size=config["vocab_held"],
                        latent_clusters=config["latent_clusters"], n_train=config["n_train"],
                        n_test=config["n_test"], seq_len=config["seq_len"], seed=int(rng.integers(1 << 30)))
    perm = _relabel(config["vocab_held"], config["buckets"], seed).astype(np.int32)
    data = [LMClientData(perm[d.tokens_train], perm[d.labels_train], perm[d.tokens_test], perm[d.labels_test],
                         d.latent_cluster) for d in data]
    init = task.init_params(jax.random.PRNGKey(program_seed))
    init["head_b"] = init["head_b"][:, np.argsort(perm)]
    client = dict(num_classes=task.buckets, task=task, local_epochs=config["local_epochs"], lr=config["lr"])
    return data, init, client


# ----------------------------------------------------------------- counts
def _layout(config: dict) -> list:
    """The row's leaves in order, ``(path, shape)``, as the delta pytree's
    sorted keys give them."""
    d, r, V, P = config["hidden_size"], config["lora_rank"], config["vocab_held"], config["num_periods"]
    width = {"wq": config["num_attention_heads"] * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]),
             "w_dkv": config["kv_lora_rank"] + config["qk_rope_head_dim"]}
    targets = sorted(config["lora_targets"])
    out = [(("head_a",), (d, r)), (("head_b",), (r, V))]
    for i in range(config["first_k_dense_replace"]):
        for t in targets:
            out += [(("prefix", f"layer{i}", t, "a"), (d, r)), (("prefix", f"layer{i}", t, "b"), (r, width[t]))]
    for t in targets:
        out += [((t, "slot0", "a"), (P, d, r)), ((t, "slot0", "b"), (P, r, width[t]))]
    return sorted(out)


def row_floats(config: dict) -> int:
    return sum(int(np.prod(shape)) for _, shape in _layout(config))


def _layers(config: dict) -> int:
    return config["first_k_dense_replace"] + config["num_periods"]


def forward_flops_per_token(config: dict) -> float:
    """Forward FLOPs one token requires: 2 per MAC of every projection and
    LoRA factor, causal attention over the sequence (half the scores on
    average), the held experts at their expected share of the top-k
    (``top_k * experts_held / n_routed_experts`` pairs) and the head."""
    d, r, S = config["hidden_size"], config["lora_rank"], config["seq_len"]
    H, nope, rope, dv, kvr = (config[k] for k in ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                                  "v_head_dim", "kv_lora_rank"))
    mla = d * H * (nope + rope) + d * (kvr + rope) + kvr * H * (nope + dv) + H * dv * d
    mla += len(config["lora_targets"]) * d * r + r * (H * (nope + rope) + kvr + rope)
    attn = H * (S + 1) / 2 * ((nope + rope) + dv)
    expert = 3 * d * config["moe_intermediate_size"]
    pairs = config["num_experts_per_tok"] * config["experts_held"] / config["n_routed_experts"]
    moe = d * config["n_routed_experts"] + (pairs + config["n_shared_experts"]) * expert
    dense = 3 * d * config["intermediate_size"]
    head = d * config["vocab_held"] + d * r + r * config["vocab_held"]
    k = config["first_k_dense_replace"]
    return 2.0 * (_layers(config) * (mla + attn) + k * dense + config["num_periods"] * moe + head)


def train_flops_per_upload(config: dict, *, head_only: bool = False) -> float:
    """Local epochs x tokens x (the forward, and the backward's input
    gradients carried down to the lowest adapter: the head alone for
    head-only fine-tuning, the whole stack below the head otherwise, about
    the forward's FLOPs; no frozen weight's gradient, no recomputation)."""
    tokens = config["local_epochs"] * config["n_train"] * config["seq_len"]
    fwd = forward_flops_per_token(config)
    d, r, V = config["hidden_size"], config["lora_rank"], config["vocab_held"]
    backward = 2.0 * (d * r + r * V) * 2 if head_only else fwd
    return tokens * (fwd + backward)


def moe_cost(config: dict, pairs: float, tokens: float, launches: int) -> tuple[float, float]:
    """(operations, bytes) the expert layers of ``launches`` training
    launches require for ``pairs`` (token, expert) pairs routed to held
    experts, summed over the expert layers, and ``tokens`` trained tokens:
    per pair, and per token in each expert layer through the shared
    experts, the three expert matmuls forward and their input gradients
    (2 FLOPs per MAC, twice); every held and shared expert weight read once
    per launch (bf16)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    per = 2 * 3 * d * f * 2
    ops = pairs * per + tokens * config["num_periods"] * config["n_shared_experts"] * per
    weights = config["num_periods"] * (config["experts_held"] + config["n_shared_experts"]) * 3 * d * f
    return float(ops), float(launches * weights * BF16)


# -------------------------------------------------------------- reference
_REF_BASE: dict = {}  # the reference's device copy of the process's base: {key: base}


def _reference_base() -> dict:
    """The base this process drew (:func:`draw_base`, relabelled), on the
    device for the reference."""
    import jax

    ((key, (_, base)),) = _TASK.items()
    if key not in _REF_BASE:
        _REF_BASE.clear()
        _REF_BASE[key] = jax.device_put(base)
    return _REF_BASE[key]


def _reference_delta(config: dict, row: np.ndarray) -> dict:
    """A flat row as the reference's delta: the head and each layer's
    adapters."""
    leaves, off = {}, 0
    for path, shape in _layout(config):
        n = int(np.prod(shape))
        leaves[path] = np.asarray(row[off:off + n], np.float32).reshape(shape)
        off += n
    layers = []
    for i in range(config["first_k_dense_replace"]):
        layers.append({t: {k: leaves[("prefix", f"layer{i}", t, k)] for k in "ab"} for t in config["lora_targets"]})
    for p in range(config["num_periods"]):
        layers.append({t: {k: leaves[(t, "slot0", k)][p] for k in "ab"} for t in config["lora_targets"]})
    return {"head": {"a": leaves[("head_a",)], "b": leaves[("head_b",)]}, "layers": layers}


def _reference_row(config: dict, delta: dict) -> np.ndarray:
    """The reference's delta as a flat row, in the row's order."""
    k0 = config["first_k_dense_replace"]
    leaves = {("head_a",): delta["head"]["a"], ("head_b",): delta["head"]["b"]}
    for t in config["lora_targets"]:
        for k in "ab":
            for i in range(k0):
                leaves[("prefix", f"layer{i}", t, k)] = delta["layers"][i][t][k]
            leaves[(t, "slot0", k)] = np.stack([delta["layers"][k0 + p][t][k]
                                                for p in range(config["num_periods"])])
    return np.concatenate([np.ravel(leaves[path]) for path, _ in _layout(config)]).astype(np.float64)


def train_reference(config: dict, base: np.ndarray, data, *, epochs: int, lr: float, head_only: bool,
                    cast=None) -> np.ndarray:
    """``base`` (a flat delta row) trained on ``data`` by the configuration's
    reference over the base this process drew; with ``cast`` (the control)
    the reference computes in bfloat16 throughout."""
    import jax.numpy as jnp

    ref = spec.load_module(BENCH_DIR / config["reference"])
    out = ref.local_train(_reference_delta(config, base), _reference_base(), data.tokens_train, data.labels_train,
                          _sizes(config), epochs=epochs, lr=lr, head_only=head_only,
                          dtype=jnp.float32 if cast is None else jnp.bfloat16)
    return _reference_row(config, out)
