"""Checkpoint loading: HuggingFace safetensors -> stacked param pytree.

Maps the HF llama-family naming scheme (model.layers.N.self_attn.q_proj...)
onto this framework's scan-stacked layout (models/llama.py): per-layer weights
are transposed to [in, out] and stacked along a leading layer dim. Handles
single-file and index-sharded checkpoints. Supports Llama-3 and Qwen2.5
families (attention biases included when present).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any

import jax.numpy as jnp
import numpy as np

from ..utils.logger import get_logger
from .config import ModelConfig

log = get_logger("loader")


class CheckpointError(Exception):
    pass


def _open_shards(path: str):
    """Yield (name, tensor-loader) for every tensor across all shards."""
    from safetensors import safe_open

    if os.path.isfile(path):
        files = [path]
    else:
        index = os.path.join(path, "model.safetensors.index.json")
        if os.path.isfile(index):
            with open(index, "r", encoding="utf-8") as f:
                weight_map: dict[str, str] = json.load(f)["weight_map"]
            files = sorted({os.path.join(path, v) for v in weight_map.values()})
        else:
            files = sorted(
                os.path.join(path, f)
                for f in os.listdir(path)
                if f.endswith(".safetensors")
            )
    if not files:
        raise CheckpointError(f"no .safetensors files under {path}")
    tensors: dict[str, Any] = {}
    for file in files:
        fh = safe_open(file, framework="numpy")
        for name in fh.keys():
            tensors[name] = (fh, name)
    return tensors


def _get(tensors: dict[str, Any], name: str) -> np.ndarray:
    if name not in tensors:
        raise CheckpointError(f"missing tensor {name} in checkpoint")
    fh, key = tensors[name]
    return fh.get_tensor(key)


def _maybe(tensors: dict[str, Any], name: str) -> np.ndarray | None:
    if name not in tensors:
        return None
    fh, key = tensors[name]
    return fh.get_tensor(key)


def _stack_linear(tensors, name_fmt: str, ids: list[int], dtype) -> jnp.ndarray:
    """HF stores [out, in]; we use [in, out]. Stack over the given layers."""
    mats = [_get(tensors, name_fmt.format(i)).T for i in ids]
    return jnp.asarray(np.stack(mats), dtype=dtype)


def _rope_interleave_to_halfsplit(dr: int) -> np.ndarray:
    """Column permutation mapping HF DeepSeek's INTERLEAVED rope pair
    layout (dims 2i/2i+1 rotate together; modeling_deepseek de-interleaves
    activations with view(.., d//2, 2).transpose before rotate_half) onto
    this framework's half-split ``apply_rope`` convention (dim j pairs
    with j + d/2). Permuting the projection's output columns once at load
    is exactly equivalent to HF's runtime de-interleave."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def _load_mla_attn_block(
    tensors, cfg: ModelConfig, layer_ids: list[int], dtype
) -> dict[str, Any]:
    """MLA attention weights (HF deepseek_v2/v3 naming) -> this
    framework's layout (models/llama._mla_attn_block):

    - ``kv_a_proj_with_mqa`` rows split into the kv latent (wdkv) and the
      shared rope key (wkr);
    - rope-part columns (of q and wkr) permuted from HF's interleaved
      pair order to the half-split order ``apply_rope`` expects;
    - ``o_proj`` columns (HF [d, H*dv]) expand to PADDED per-head rows
      [H*(dn+dr), d] — the pad rows multiply the v zero-padding and are
      zeroed here.
    """
    m = cfg.mla
    d = cfg.hidden_size
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    dq = dn + dr
    rkv = m.kv_lora_rank

    def vector(name_fmt: str) -> jnp.ndarray:
        vecs = [_get(tensors, name_fmt.format(i)) for i in layer_ids]
        return jnp.asarray(np.stack(vecs), dtype=dtype)

    def linear(name_fmt: str) -> jnp.ndarray:
        return _stack_linear(tensors, name_fmt, layer_ids, dtype)

    if not layer_ids:
        block: dict[str, Any] = {
            "attn_norm": jnp.zeros((0, d), dtype),
            "wdkv": jnp.zeros((0, d, rkv), dtype),
            "wkr": jnp.zeros((0, d, dr), dtype),
            "kv_norm": jnp.zeros((0, rkv), dtype),
            "wukv": jnp.zeros((0, rkv, H * (dn + dv)), dtype),
            "wo": jnp.zeros((0, H * dq, d), dtype),
            "mlp_norm": jnp.zeros((0, d), dtype),
        }
        if m.q_lora_rank:
            block["wdq"] = jnp.zeros((0, d, m.q_lora_rank), dtype)
            block["q_norm"] = jnp.zeros((0, m.q_lora_rank), dtype)
            block["wuq"] = jnp.zeros((0, m.q_lora_rank, H * dq), dtype)
        else:
            block["wq"] = jnp.zeros((0, d, H * dq), dtype)
        return block

    perm = _rope_interleave_to_halfsplit(dr)

    def fix_q_rope(w: np.ndarray) -> np.ndarray:
        """Permute each head's rope columns of a [in, H*dq] q projection."""
        w = w.reshape(w.shape[0], H, dq)
        return np.concatenate(
            [w[..., :dn], w[..., dn:][..., perm]], axis=-1
        ).reshape(w.shape[0], H * dq)

    block = {
        "attn_norm": vector("model.layers.{}.input_layernorm.weight"),
        "kv_norm": vector("model.layers.{}.self_attn.kv_a_layernorm.weight"),
        "wukv": linear("model.layers.{}.self_attn.kv_b_proj.weight"),
        "mlp_norm": vector("model.layers.{}.post_attention_layernorm.weight"),
    }
    def q_linear(hf_name: str) -> jnp.ndarray:
        return jnp.asarray(
            np.stack([
                fix_q_rope(
                    _get(
                        tensors,
                        f"model.layers.{i}.self_attn.{hf_name}.weight",
                    ).T
                )
                for i in layer_ids
            ]),
            dtype=dtype,
        )

    if m.q_lora_rank:
        block["wdq"] = linear("model.layers.{}.self_attn.q_a_proj.weight")
        block["q_norm"] = vector(
            "model.layers.{}.self_attn.q_a_layernorm.weight"
        )
        block["wuq"] = q_linear("q_b_proj")
    else:
        block["wq"] = q_linear("q_proj")
    # kv_a_proj_with_mqa: HF [rkv + dr, d] -> ours [d, rkv] + [d, dr]
    # (rope columns permuted to half-split order).
    wdkv, wkr = [], []
    for i in layer_ids:
        w = _get(
            tensors, f"model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight"
        ).T  # [d, rkv + dr]
        wdkv.append(w[:, :rkv])
        wkr.append(w[:, rkv:][:, perm])
    block["wdkv"] = jnp.asarray(np.stack(wdkv), dtype=dtype)
    block["wkr"] = jnp.asarray(np.stack(wkr), dtype=dtype)
    # o_proj: HF [d, H*dv] -> ours transposed [H*dv, d], expanded to
    # [H*(dn+dr), d] with zero pad rows per head.
    wo = []
    for i in layer_ids:
        w = _get(tensors, f"model.layers.{i}.self_attn.o_proj.weight").T
        w = w.reshape(H, dv, d)
        pad = np.zeros((H, dq - dv, d), w.dtype)
        wo.append(np.concatenate([w, pad], axis=1).reshape(H * dq, d))
    block["wo"] = jnp.asarray(np.stack(wo), dtype=dtype)
    return block


def _load_attn_block(
    tensors, cfg: ModelConfig, layer_ids: list[int], dtype
) -> dict[str, Any]:
    """Attention weights + norms for an explicit list of HF layer indices,
    stacked in that order. An empty id list (e.g. moe_layer_start=0) yields
    zero-length stacks matching init_params' shapes."""
    if cfg.mla is not None:
        return _load_mla_attn_block(tensors, cfg, layer_ids, dtype)
    d, q, kv = cfg.hidden_size, cfg.q_size, cfg.kv_size
    if not layer_ids:
        block = {
            "attn_norm": jnp.zeros((0, d), dtype),
            "wq": jnp.zeros((0, d, q), dtype),
            "wk": jnp.zeros((0, d, kv), dtype),
            "wv": jnp.zeros((0, d, kv), dtype),
            "wo": jnp.zeros((0, q, d), dtype),
            "mlp_norm": jnp.zeros((0, d), dtype),
        }
        if cfg.attn_bias:
            block["bq"] = jnp.zeros((0, q), dtype)
            block["bk"] = jnp.zeros((0, kv), dtype)
            block["bv"] = jnp.zeros((0, kv), dtype)
        if cfg.qk_norm:
            block["qn"] = jnp.zeros((0, cfg.head_dim_), dtype)
            block["kn"] = jnp.zeros((0, cfg.head_dim_), dtype)
        return block

    def linear(name_fmt: str) -> jnp.ndarray:
        return _stack_linear(tensors, name_fmt, layer_ids, dtype)

    def vector(name_fmt: str) -> jnp.ndarray:
        vecs = [_get(tensors, name_fmt.format(i)) for i in layer_ids]
        return jnp.asarray(np.stack(vecs), dtype=dtype)

    block: dict[str, Any] = {
        "attn_norm": vector("model.layers.{}.input_layernorm.weight"),
        "wq": linear("model.layers.{}.self_attn.q_proj.weight"),
        "wk": linear("model.layers.{}.self_attn.k_proj.weight"),
        "wv": linear("model.layers.{}.self_attn.v_proj.weight"),
        "wo": linear("model.layers.{}.self_attn.o_proj.weight"),
        "mlp_norm": vector("model.layers.{}.post_attention_layernorm.weight"),
    }
    if cfg.attn_bias:
        block["bq"] = vector("model.layers.{}.self_attn.q_proj.bias")
        block["bk"] = vector("model.layers.{}.self_attn.k_proj.bias")
        block["bv"] = vector("model.layers.{}.self_attn.v_proj.bias")
    if cfg.qk_norm:
        # Qwen3 per-head q/k RMSNorm weights ([head_dim] each).
        block["qn"] = vector("model.layers.{}.self_attn.q_norm.weight")
        block["kn"] = vector("model.layers.{}.self_attn.k_norm.weight")
    return block


def load_checkpoint(
    path: str, cfg: ModelConfig, dtype: Any = jnp.bfloat16
) -> dict[str, Any]:
    """Load an HF llama/qwen/deepseek-moe checkpoint into the stacked param
    layout. MoE layers use the DeepSeek naming scheme: ``mlp.gate.weight``
    (router), ``mlp.experts.{e}.{gate,up,down}_proj.weight``, and fused
    ``mlp.shared_experts.{gate,up,down}_proj.weight``.

    This is the slow cold-start path: a host-side parse + transpose +
    restack of every tensor. ``Engine.from_snapshot`` bypasses it
    entirely — snapshot restore (serving/snapshot/restore.py) memory-maps
    leaves already in this stacked device layout."""
    if (cfg.mixer_period or cfg.attn_output_gate or not cfg.use_rope
            or cfg.post_norm or cfg.qk_norm_whole):
        raise NotImplementedError(
            "load_checkpoint: no loader for a solar_open2, olmo_hybrid or "
            "jamba checkpoint (a layer pattern, gated or NoPE attention, "
            "linear-attention or Mamba layers, the post-norm block): its "
            "checkpoint's tensor names are not public here; "
            "config_from_hf reads its config.json, and weights come seeded"
        )
    t0 = time.perf_counter()
    tensors = _open_shards(path)
    L = cfg.num_layers
    Ld = cfg.moe_layer_start if cfg.moe is not None else L
    dense_ids, moe_ids = list(range(Ld)), list(range(Ld, L))

    def linear_ids(name_fmt: str, ids: list[int]) -> jnp.ndarray:
        return _stack_linear(tensors, name_fmt, ids, dtype)

    layers = _load_attn_block(tensors, cfg, dense_ids, dtype)
    f = cfg.intermediate_size
    if dense_ids:
        layers.update(
            {
                "wg": linear_ids("model.layers.{}.mlp.gate_proj.weight", dense_ids),
                "wu": linear_ids("model.layers.{}.mlp.up_proj.weight", dense_ids),
                "wd": linear_ids("model.layers.{}.mlp.down_proj.weight", dense_ids),
            }
        )
    else:
        d = cfg.hidden_size
        layers.update(
            {
                "wg": jnp.zeros((0, d, f), dtype),
                "wu": jnp.zeros((0, d, f), dtype),
                "wd": jnp.zeros((0, f, d), dtype),
            }
        )

    params: dict[str, Any] = {
        "embed": jnp.asarray(_get(tensors, "model.embed_tokens.weight"), dtype=dtype),
        "layers": layers,
        "final_norm": jnp.asarray(_get(tensors, "model.norm.weight"), dtype=dtype),
    }

    if moe_ids:
        E = cfg.moe.num_experts
        moe_layers = _load_attn_block(tensors, cfg, moe_ids, dtype)

        def experts(proj: str) -> jnp.ndarray:
            # [Lm, E, in, out]
            mats = [
                np.stack([
                    _get(
                        tensors,
                        f"model.layers.{i}.mlp.experts.{e}.{proj}.weight",
                    ).T
                    for e in range(E)
                ])
                for i in moe_ids
            ]
            return jnp.asarray(np.stack(mats), dtype=dtype)

        moe_layers.update(
            {
                "router": jnp.asarray(
                    np.stack([
                        _get(tensors, f"model.layers.{i}.mlp.gate.weight").T
                        for i in moe_ids
                    ]),
                    dtype=jnp.float32,
                ),
                "eg": experts("gate_proj"),
                "eu": experts("up_proj"),
                "ed": experts("down_proj"),
            }
        )
        if cfg.moe.scoring_func == "sigmoid":
            # V3 noaux_tc selection bias (HF e_score_correction_bias).
            moe_layers["router_bias"] = jnp.asarray(
                np.stack([
                    _get(
                        tensors,
                        f"model.layers.{i}.mlp.gate.e_score_correction_bias",
                    )
                    for i in moe_ids
                ]),
                dtype=jnp.float32,
            )
        if cfg.moe.num_shared_experts:
            moe_layers["sg"] = linear_ids(
                "model.layers.{}.mlp.shared_experts.gate_proj.weight", moe_ids
            )
            moe_layers["su"] = linear_ids(
                "model.layers.{}.mlp.shared_experts.up_proj.weight", moe_ids
            )
            moe_layers["sd"] = linear_ids(
                "model.layers.{}.mlp.shared_experts.down_proj.weight", moe_ids
            )
        params["moe_layers"] = moe_layers
    head = _maybe(tensors, "lm_head.weight")
    if cfg.tie_embeddings or head is None:
        if not cfg.tie_embeddings and head is None:
            raise CheckpointError(
                "checkpoint has no lm_head.weight but config does not tie embeddings"
            )
    else:
        params["lm_head"] = jnp.asarray(head.T, dtype=dtype)

    # Shape validation against the config.
    v, d = params["embed"].shape
    if v != cfg.vocab_size or d != cfg.hidden_size:
        raise CheckpointError(
            f"embed shape {(v, d)} does not match config "
            f"({cfg.vocab_size}, {cfg.hidden_size})"
        )
    log.info(
        "checkpoint %s parsed/restacked in %.1f s", path,
        time.perf_counter() - t0,
    )
    return params


_ATTN_NAME_MAP = {
    "attn_norm": "model.layers.{}.input_layernorm.weight",
    "wq": "model.layers.{}.self_attn.q_proj.weight",
    "wk": "model.layers.{}.self_attn.k_proj.weight",
    "wv": "model.layers.{}.self_attn.v_proj.weight",
    "wo": "model.layers.{}.self_attn.o_proj.weight",
    "mlp_norm": "model.layers.{}.post_attention_layernorm.weight",
    "bq": "model.layers.{}.self_attn.q_proj.bias",
    "bk": "model.layers.{}.self_attn.k_proj.bias",
    "bv": "model.layers.{}.self_attn.v_proj.bias",
    "qn": "model.layers.{}.self_attn.q_norm.weight",
    "kn": "model.layers.{}.self_attn.k_norm.weight",
}

_DENSE_MLP_NAME_MAP = {
    "wg": "model.layers.{}.mlp.gate_proj.weight",
    "wu": "model.layers.{}.mlp.up_proj.weight",
    "wd": "model.layers.{}.mlp.down_proj.weight",
}

_SHARED_NAME_MAP = {
    "sg": "model.layers.{}.mlp.shared_experts.gate_proj.weight",
    "su": "model.layers.{}.mlp.shared_experts.up_proj.weight",
    "sd": "model.layers.{}.mlp.shared_experts.down_proj.weight",
}

_EXPERT_NAME_MAP = {
    "eg": "model.layers.{}.mlp.experts.{}.gate_proj.weight",
    "eu": "model.layers.{}.mlp.experts.{}.up_proj.weight",
    "ed": "model.layers.{}.mlp.experts.{}.down_proj.weight",
}


def _dump_block(
    flat: dict[str, np.ndarray],
    block: dict[str, Any],
    name_map: dict[str, str],
    layer_offset: int,
) -> None:
    for key, fmt in name_map.items():
        if key not in block:
            continue
        stacked = np.asarray(block[key].astype(jnp.float32))
        for i in range(stacked.shape[0]):
            mat = stacked[i]
            if mat.ndim == 2:
                mat = mat.T  # back to HF [out, in]
            flat[fmt.format(i + layer_offset)] = np.ascontiguousarray(mat)


def _dump_mla_block(
    flat: dict[str, np.ndarray],
    block: dict[str, Any],
    layer_offset: int,
    cfg: ModelConfig,
) -> None:
    """Inverse of ``_load_mla_attn_block``: recombine wdkv/wkr into
    kv_a_proj_with_mqa and strip wo's pad rows back to [d, H*dv]."""
    m = cfg.mla
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    dq = dn + dr
    L = block["mlp_norm"].shape[0]
    name_map = {
        "attn_norm": "model.layers.{}.input_layernorm.weight",
        "mlp_norm": "model.layers.{}.post_attention_layernorm.weight",
        "kv_norm": "model.layers.{}.self_attn.kv_a_layernorm.weight",
        "wukv": "model.layers.{}.self_attn.kv_b_proj.weight",
        "wdq": "model.layers.{}.self_attn.q_a_proj.weight",
        "q_norm": "model.layers.{}.self_attn.q_a_layernorm.weight",
    }
    _dump_block(flat, block, name_map, layer_offset)
    # Rope columns back to HF's interleaved order (inverse of the load
    # permutation).
    inv = np.argsort(_rope_interleave_to_halfsplit(dr))

    def unfix_q_rope(w: np.ndarray) -> np.ndarray:
        w = w.reshape(w.shape[0], H, dq)
        return np.concatenate(
            [w[..., :dn], w[..., dn:][..., inv]], axis=-1
        ).reshape(w.shape[0], H * dq)

    q_key, q_name = (
        ("wuq", "q_b_proj") if "wuq" in block else ("wq", "q_proj")
    )
    qw = np.asarray(block[q_key].astype(jnp.float32))
    wdkv = np.asarray(block["wdkv"].astype(jnp.float32))
    wkr = np.asarray(block["wkr"].astype(jnp.float32))
    wo = np.asarray(block["wo"].astype(jnp.float32))
    for i in range(L):
        li = i + layer_offset
        flat[f"model.layers.{li}.self_attn.{q_name}.weight"] = (
            np.ascontiguousarray(unfix_q_rope(qw[i]).T)
        )
        flat[f"model.layers.{li}.self_attn.kv_a_proj_with_mqa.weight"] = (
            np.ascontiguousarray(
                np.concatenate([wdkv[i], wkr[i][:, inv]], axis=1).T
            )
        )
        unpadded = wo[i].reshape(H, dq, -1)[:, :dv].reshape(H * dv, -1)
        flat[f"model.layers.{li}.self_attn.o_proj.weight"] = (
            np.ascontiguousarray(unpadded.T)
        )


def save_checkpoint(
    path: str, params: dict[str, Any], cfg: ModelConfig | None = None
) -> None:
    """Write params back out as a single HF-style safetensors file (testing
    and fine-tune export). MoE stacks round-trip through the DeepSeek naming
    scheme ``load_checkpoint`` reads; MLA models additionally need ``cfg``
    (the pad/split geometry is not recoverable from shapes alone)."""
    from safetensors.numpy import save_file

    flat: dict[str, np.ndarray] = {}
    Ld = params["layers"]["mlp_norm"].shape[0]
    mla = cfg.mla if cfg is not None else None
    if "wdkv" in params["layers"] and mla is None:
        raise ValueError("saving an MLA checkpoint requires cfg")
    if mla is not None:
        _dump_mla_block(flat, params["layers"], 0, cfg)
        _dump_block(flat, params["layers"], _DENSE_MLP_NAME_MAP, 0)
    else:
        _dump_block(
            flat, params["layers"],
            {**_ATTN_NAME_MAP, **_DENSE_MLP_NAME_MAP}, 0,
        )
    if "moe_layers" in params:
        moe = params["moe_layers"]
        if mla is not None:
            _dump_mla_block(flat, moe, Ld, cfg)
            _dump_block(flat, moe, _SHARED_NAME_MAP, Ld)
        else:
            _dump_block(flat, moe, {**_ATTN_NAME_MAP, **_SHARED_NAME_MAP}, Ld)
        if "router_bias" in moe:
            rb = np.asarray(moe["router_bias"].astype(jnp.float32))
            for i in range(rb.shape[0]):
                flat[
                    f"model.layers.{i + Ld}.mlp.gate.e_score_correction_bias"
                ] = np.ascontiguousarray(rb[i])
        router = np.asarray(moe["router"].astype(jnp.float32))
        for i in range(router.shape[0]):
            flat[f"model.layers.{i + Ld}.mlp.gate.weight"] = (
                np.ascontiguousarray(router[i].T)
            )
        for key, fmt in _EXPERT_NAME_MAP.items():
            stacked = np.asarray(moe[key].astype(jnp.float32))
            for i in range(stacked.shape[0]):
                for e in range(stacked.shape[1]):
                    flat[fmt.format(i + Ld, e)] = np.ascontiguousarray(
                        stacked[i, e].T
                    )
    flat["model.embed_tokens.weight"] = np.asarray(
        params["embed"].astype(jnp.float32)
    )
    flat["model.norm.weight"] = np.asarray(params["final_norm"].astype(jnp.float32))
    if "lm_head" in params:
        flat["lm_head.weight"] = np.ascontiguousarray(
            np.asarray(params["lm_head"].astype(jnp.float32)).T
        )
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    save_file(flat, path)
