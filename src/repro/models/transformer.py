"""The full model stack: embeddings -> scanned layer stack -> head.

Heterogeneous depth patterns (DeepSeek's leading dense layers, Jamba's
1-attention-per-8 interleave with MoE every other layer) are handled by a
*stage plan*: an unrolled prefix plus one ``lax.scan`` over super-blocks whose
sub-layer descriptors repeat periodically.  The scan keeps HLO size O(1) in
depth — required to compile 61-88-layer models against 512 host devices.

Entry points:
  init_params / param_specs       -- parameters + logical PartitionSpecs
  forward / forward_embeds        -- full-sequence logits (train & prefill)
  init_cache / cache_specs        -- decode state (KV / latent-KV / SSM)
  decode_step                     -- one-token step with cache
  loss_fn                         -- next-token CE (+ optional MTP aux loss)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models.config import ModelConfig

Params = Dict[str, Any]

from repro.models import flags as F

# Back-compat setters (tests/launchers import these from here too).
set_remat = F.set_remat
set_unroll = F.set_unroll


def _maybe_remat(fn):
    if F.REMAT == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if F.REMAT == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    kind: str   # attn | ssm
    ffn: str    # mlp | moe | none


def layer_descs(cfg: ModelConfig) -> List[LayerDesc]:
    kinds = cfg.layer_kinds()
    out = []
    for i in range(cfg.n_layers):
        if cfg.layer_has_moe(i):
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "mlp"
        else:
            ffn = "none"
        out.append(LayerDesc(kinds[i], ffn))
    return out


def stage_plan(cfg: ModelConfig) -> Tuple[List[LayerDesc], List[LayerDesc], int]:
    """(prefix descs, period descs, n_blocks): layers = prefix + period*n."""
    descs = layer_descs(cfg)
    npre = cfg.first_dense_layers
    rest = descs[npre:]
    if not rest:
        return descs, [], 0
    for p in range(1, len(rest) + 1):
        if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
            return descs[:npre], rest[:p], len(rest) // p
    return descs[:npre], rest, 1


# ---------------------------------------------------------------------------
# per-layer init / specs / fwd
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, desc: LayerDesc, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if desc.kind == "attn":
        p["mixer"] = (L.init_mla(k1, cfg, dtype) if cfg.use_mla
                      else L.init_attention(k1, cfg, dtype))
    else:
        p["mixer"] = L.init_mamba2(k1, cfg, dtype)
    if desc.ffn != "none":
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p["ffn"] = (L.init_moe(k2, cfg, dtype) if desc.ffn == "moe"
                    else L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype))
    return p


def _layer_specs(cfg: ModelConfig, desc: LayerDesc, tp: int) -> Params:
    p: Params = {"ln1": P(None)}
    if desc.kind == "attn":
        p["mixer"] = (L.specs_mla(cfg, tp) if cfg.use_mla
                      else L.specs_attention(cfg, tp))
    else:
        p["mixer"] = L.specs_mamba2(cfg, tp)
    if desc.ffn != "none":
        p["ln2"] = P(None)
        p["ffn"] = (L.specs_moe(cfg, tp) if desc.ffn == "moe"
                    else L.specs_mlp(cfg.d_ff, cfg.mlp_act, tp))
    return p


def _layer_fwd(cfg: ModelConfig, desc: LayerDesc, p: Params, x: jax.Array,
               positions: jax.Array, cache: Optional[Params],
               cur_len) -> Tuple[jax.Array, Optional[Params]]:
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if desc.kind == "attn":
        fwd = L.mla_fwd if cfg.use_mla else L.attention_fwd
        mix, new_cache = fwd(p["mixer"], h, positions, cfg,
                             cache=cache, cur_len=cur_len)
    else:
        mix, new_cache = L.mamba2_fwd(p["mixer"], h, cfg, state=cache)
    x = x + mix
    x = constrain(x, "dp", "sp", None)
    if desc.ffn != "none":
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        y = (L.moe_fwd(p["ffn"], h2, cfg) if desc.ffn == "moe"
             else L.mlp_fwd(p["ffn"], h2, cfg.mlp_act))
        x = x + y
        x = constrain(x, "dp", "sp", None)
    return x, new_cache


def _layer_cache(cfg: ModelConfig, desc: LayerDesc, batch: int, max_len: int,
                 dtype) -> Optional[Params]:
    if desc.kind == "attn":
        if cfg.use_mla:
            return L.init_mla_cache(cfg, batch, max_len, dtype)
        return L.init_attention_cache(cfg, batch, max_len, dtype)
    return L.init_mamba2_state(cfg, batch, dtype)


def _layer_cache_specs(cfg: ModelConfig, desc: LayerDesc, tp: int) -> Params:
    if desc.kind == "attn":
        if cfg.use_mla:
            return L.specs_mla_cache(cfg, tp)
        return L.specs_attention_cache(cfg, tp)
    return L.specs_mamba2_state(cfg, tp)


# ---------------------------------------------------------------------------
# whole-model init / specs
# ---------------------------------------------------------------------------

def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dtype = _dtype(cfg)
    prefix, period, nblocks = stage_plan(cfg)
    kemb, khead, kpre, kstk, kmtp = jax.random.split(key, 5)
    params: Params = {
        "embed": (jax.random.truncated_normal(
            kemb, -2, 2, (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
        ).astype(dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(khead, cfg.d_model, cfg.vocab, dtype)
    params["prefix"] = [
        _init_layer(k, cfg, d, dtype)
        for k, d in zip(jax.random.split(kpre, max(len(prefix), 1)), prefix)]
    if nblocks:
        def one_block(k):
            ks = jax.random.split(k, len(period))
            return {f"sub{j}": _init_layer(ks[j], cfg, period[j], dtype)
                    for j in range(len(period))}
        blocks = [one_block(k) for k in jax.random.split(kstk, nblocks)]
        params["stack"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    else:
        params["stack"] = {}
    if cfg.mtp_depth:
        km1, km2, km3 = jax.random.split(kmtp, 3)
        params["mtp"] = {
            "proj": L.dense_init(km1, 2 * cfg.d_model, cfg.d_model, dtype),
            "block": _init_layer(km2, cfg, LayerDesc("attn", "mlp"), dtype),
            "norm": jnp.ones((cfg.d_model,), dtype),
        }
    return params


def param_specs(cfg: ModelConfig, tp: int) -> Params:
    prefix, period, nblocks = stage_plan(cfg)
    vshard = "tp" if cfg.vocab % max(tp, 1) == 0 else None
    specs: Params = {
        "embed": P(vshard, "fsdp"),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("fsdp", vshard)
    specs["prefix"] = [_layer_specs(cfg, d, tp) for d in prefix]
    if nblocks:
        block = {f"sub{j}": _layer_specs(cfg, period[j], tp)
                 for j in range(len(period))}
        specs["stack"] = jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), block,
            is_leaf=lambda s: isinstance(s, P))
    else:
        specs["stack"] = {}
    if cfg.mtp_depth:
        specs["mtp"] = {
            "proj": P("fsdp", None),
            "block": _layer_specs(cfg, LayerDesc("attn", "mlp"), tp),
            "norm": P(None),
        }
    return specs


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _stack_fwd(params: Params, cfg: ModelConfig, x: jax.Array,
               positions: jax.Array, cache: Optional[Params],
               cur_len) -> Tuple[jax.Array, Optional[Params]]:
    prefix, period, nblocks = stage_plan(cfg)
    new_cache: Params = {"prefix": [], "stack": {}}
    for i, desc in enumerate(prefix):
        c = cache["prefix"][i] if cache is not None else None
        x, nc = _layer_fwd(cfg, desc, params["prefix"][i], x, positions, c, cur_len)
        new_cache["prefix"].append(nc)
    if nblocks:
        if cache is None:
            def body(h, pslice):
                for j, desc in enumerate(period):
                    h, _ = _layer_fwd(cfg, desc, pslice[f"sub{j}"], h,
                                      positions, None, None)
                return h, None
            if F.UNROLL:
                body = _maybe_remat(body)
                for bi in range(nblocks):
                    x, _ = body(x, jax.tree.map(lambda a: a[bi], params["stack"]))
            else:
                x, _ = lax.scan(_maybe_remat(body), x, params["stack"])
        else:
            def body(h, slc):
                pslice, cslice = slc
                ncs = {}
                for j, desc in enumerate(period):
                    h, nc = _layer_fwd(cfg, desc, pslice[f"sub{j}"], h,
                                       positions, cslice[f"sub{j}"], cur_len)
                    ncs[f"sub{j}"] = nc
                return h, ncs
            if F.UNROLL:
                outs = []
                for bi in range(nblocks):
                    x, nc = body(x, jax.tree.map(
                        lambda a: a[bi], (params["stack"], cache["stack"])))
                    outs.append(nc)
                new_cache["stack"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *outs)
            else:
                x, new_stack = lax.scan(body, x, (params["stack"], cache["stack"]))
                new_cache["stack"] = new_stack
    return x, (new_cache if cache is not None else None)


def hidden_embeds(params: Params, embeds: jax.Array, cfg: ModelConfig, *,
                  positions: Optional[jax.Array] = None,
                  cache: Optional[Params] = None,
                  cur_len=None) -> Tuple[jax.Array, Optional[Params]]:
    """embeds: (B, T, D) -> (final hidden states (B, T, D), new cache)."""
    b, t, _ = embeds.shape
    if positions is None:
        if cur_len is not None:
            positions = jnp.broadcast_to(cur_len, (b, t))
        else:
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    x = constrain(embeds, "dp", "sp", None)
    x, new_cache = _stack_fwd(params, cfg, x, positions, cache, cur_len)
    return x, new_cache


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = L.apply_linear(head, x)
    return constrain(logits, "dp", None, "tp")


def forward_embeds(params: Params, embeds: jax.Array, cfg: ModelConfig, *,
                   positions: Optional[jax.Array] = None,
                   cache: Optional[Params] = None,
                   cur_len=None) -> Tuple[jax.Array, Optional[Params]]:
    """embeds: (B, T, D) -> (logits (B, T, V), new cache)."""
    x, new_cache = hidden_embeds(params, embeds, cfg, positions=positions,
                                 cache=cache, cur_len=cur_len)
    return _head(params, cfg, x), new_cache


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
            cache: Optional[Params] = None,
            cur_len=None) -> Tuple[jax.Array, Optional[Params]]:
    """tokens: (B, T) int32 -> (logits, new cache)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(_dtype(cfg))
    return forward_embeds(params, x, cfg, cache=cache, cur_len=cur_len)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dtype = _dtype(cfg)
    prefix, period, nblocks = stage_plan(cfg)
    cache: Params = {
        "prefix": [_layer_cache(cfg, d, batch, max_len, dtype) for d in prefix],
        "stack": {},
    }
    if nblocks:
        block = {f"sub{j}": _layer_cache(cfg, period[j], batch, max_len, dtype)
                 for j in range(len(period))}
        cache["stack"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (nblocks,) + x.shape), block)
    return cache


def cache_specs(cfg: ModelConfig, tp: int) -> Params:
    prefix, period, nblocks = stage_plan(cfg)
    specs: Params = {
        "prefix": [_layer_cache_specs(cfg, d, tp) for d in prefix],
        "stack": {},
    }
    if nblocks:
        block = {f"sub{j}": _layer_cache_specs(cfg, period[j], tp)
                 for j in range(len(period))}
        specs["stack"] = jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), block,
            is_leaf=lambda s: isinstance(s, P))
    return specs


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Params, cur_len) -> Tuple[jax.Array, Params]:
    """One decode step.  tokens: (B, 1); cur_len: () int32 current length."""
    logits, new_cache = forward(params, tokens, cfg, cache=cache, cur_len=cur_len)
    return logits[:, -1], new_cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    return (lse - gold).mean()


def _embed_gather(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Embedding rows for ``ids``.  Under explicit sharding the output
    follows the ids' sharding: JAX will not infer it from a table sharded
    over both of its axes."""
    if jax.typeof(table).sharding.mesh.empty:
        return jnp.take(table, ids, axis=0)
    out = jax.sharding.NamedSharding(jax.typeof(table).sharding.mesh,
                                     P(*jax.typeof(ids).sharding.spec, None))
    return table.at[ids].get(out_sharding=out)


def loss_fn(params: Params, tokens: jax.Array, labels: jax.Array,
            cfg: ModelConfig, *, embeds: Optional[jax.Array] = None) -> jax.Array:
    """Next-token CE; DeepSeek-style MTP aux head adds a 2-ahead term."""
    if embeds is None:
        embeds = _embed_gather(params["embed"], tokens).astype(_dtype(cfg))
        use_mtp = bool(cfg.mtp_depth)
    else:
        use_mtp = False
    h, _ = hidden_embeds(params, embeds, cfg)
    logits = _head(params, cfg, h)
    loss = _xent(logits, labels)
    if use_mtp:
        # Predict labels[t+1] from (h_t, emb(labels_t)): one extra block.
        nxt = _embed_gather(params["embed"], labels).astype(_dtype(cfg))
        z = jnp.concatenate([L.rms_norm(h, params["mtp"]["norm"], cfg.norm_eps),
                             nxt], axis=-1)
        z = L.apply_linear(params["mtp"]["proj"], z)
        b, t, _ = z.shape
        pos = jnp.broadcast_to(jnp.arange(t), (b, t))
        z, _ = _layer_fwd(cfg, LayerDesc("attn", "mlp"), params["mtp"]["block"],
                          z, pos, None, None)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        mtp_logits = L.apply_linear(head, z)
        mtp_labels = jnp.concatenate([labels[:, 1:], labels[:, -1:]], axis=1)
        loss = loss + 0.3 * _xent(mtp_logits, mtp_labels)
    return loss
