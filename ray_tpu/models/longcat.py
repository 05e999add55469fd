"""LongCat-Flash's language model: the second model kind ``serve.llm`` runs.

One layer holds two latent attentions (MLA), two dense SwiGLU MLPs and one
expert layer whose output skips over the second half of the layer (the
shortcut-connected expert layer: in a deployment its exchange overlaps the
dense path). ``N`` is RMSNorm:

    a = x + MLA_0(N(x))          u = N(a)          m = MoE(u)
    b = a + FFN_0(u)
    c = b + MLA_1(N(b))
    y = c + FFN_1(N(c)) + m

The expert layer is ``models/moe.py`` (a router over routed and zero-compute
experts; this chip's share of the routed ones), the attention
``ops/latent_attention.py``. Key names follow the published ``config.json``.

The paged cache is this model's own shape: one row a position a attention of
``kv_lora_rank + qk_rope_head_dim`` values (the latent after its norm and
scale, and the rotated shared key), ``2 x num_layers`` attentions. The engine
asks this module for the pool and for a block's bytes
(``init_paged_pool``, ``paged_block_bytes``), and runs the same three programs
as ``generation.make_paged_fns`` gives a ``TransformerConfig``. The pool also
carries ``moe_counts``: what the decode steps' expert layers counted of their
routing, summed on the device (``routing_counts`` copies them out).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.generation import _kv_storage_dtype
from ray_tpu.ops.latent_attention import latent_decode_attention, latent_prefill_attention, rope_interleaved
from ray_tpu.ops.layers import rms_norm, swiglu

KIND = "longcat"


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    """Published keys (meituan-longcat ``config.json`` names) plus this chip's
    share of each layer's routed experts: ``experts_held`` of the
    ``n_routed_experts``, from ``expert_offset``. The router keeps all
    ``n_routed_experts + zero_expert_num`` outputs whatever is held."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.n_routed_experts if self.experts_held is None else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 <= self.expert_offset <= self.n_routed_experts - held:
            raise ValueError(f"experts {self.expert_offset}..{self.expert_offset + held} are not among "
                             f"{self.n_routed_experts}")

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def cache_row(self) -> int:
        """Values one position holds in one attention's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_stored(self) -> int:
        """``cache_row`` rounded up to the TPU's 128 lanes. A pool whose rows
        are no multiple of 128 wide gets another device layout than the one
        the programs compute in, and is re-laid out whole, in and out, every
        step (576 values: 1.2 GB moved a step); the padding is never read."""
        return -(-self.cache_row // 128) * 128

    @property
    def scale_q(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def scale_kv(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0


def init_params(key, cfg: LongcatConfig) -> Dict[str, Any]:
    """Seeded weights, stacked over layers (and over a layer's two attentions
    and two MLPs), by the recipe the benchmark's family seeds with
    (``benchmarks/families/longcat.py`` says why each): 1/sqrt(fan-in), the
    embedding 0.02, the dense paths' projections into the residual stream
    scaled down by sqrt(2 x layers), the two latent norms at ``1 / scale_q``
    and ``1 / scale_kv``, the expert layer as ``moe.init_expert_params`` seeds
    it. Three of an attention's matrices are kept the way the decode step
    reads them, so that no step re-lays them out: ``wqb`` (heads x (d_n +
    d_r), r_q) and ``wkva`` (r_kv + d_r, D) with the contraction last,
    ``wkvb`` (heads, r_kv, d_n + d_v) a matrix a head."""
    L, D, F, H = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_attention_heads
    rq, rkv, dn, dr, dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
    keys = iter(jax.random.split(key, 16))
    s_res = (2 * L) ** -0.5

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(cfg.dtype)

    experts = jax.vmap(lambda k: moe.init_expert_params(
        k, D, cfg.expert_ffn_hidden_size, cfg.experts_held, cfg.n_routed_experts + cfg.zero_expert_num,
        cfg.dtype))(jax.random.split(next(keys), L))
    return {
        "embed": normal((cfg.vocab_size, D), 0.02),
        "in_norm": jnp.ones((L, 2, D), jnp.float32),
        "post_norm": jnp.ones((L, 2, D), jnp.float32),
        "wqa": normal((L, 2, D, rq), D ** -0.5),
        "qa_norm": jnp.full((L, 2, rq), 1 / cfg.scale_q, jnp.float32),
        "wqb": normal((L, 2, H * (dn + dr), rq), rq ** -0.5),
        "wkva": normal((L, 2, rkv + dr, D), D ** -0.5),
        "kva_norm": jnp.full((L, 2, rkv), 1 / cfg.scale_kv, jnp.float32),
        "wkvb": normal((L, 2, H, rkv, dn + dv), rkv ** -0.5),
        "wo": normal((L, 2, H * dv, D), (H * dv) ** -0.5 * s_res),
        "w_gate": normal((L, 2, D, F), D ** -0.5),
        "w_up": normal((L, 2, D, F), D ** -0.5),
        "w_down": normal((L, 2, F, D), F ** -0.5 * s_res),
        **experts,
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, cfg.vocab_size), D ** -0.5),
    }


_UNSTACKED = ("embed", "unembed", "final_norm")
_PER_LAYER = ("router", "router_bias")  # (layers, ...): a layer's slice is small
_EXPERTS = ("e_gate", "e_up", "e_down")  # (layers, held, ...): read in place by the grouped matmul


# -- the paged pool ------------------------------------------------------------


def init_paged_pool(cfg: LongcatConfig, num_blocks: int, block_size: int) -> Dict:
    """``latent``: (attentions, num_blocks, block_size, cache_row_stored),
    block 0 the null block, 16-bit values held as raw bits as in
    ``generation.init_paged_pool``. A block is the unit the decode step
    gathers. ``moe_counts``: ``moe.COUNTS`` summed over the layers and decode
    steps so far, modulo 2**32."""
    shape = (2 * cfg.num_layers, num_blocks, block_size, cfg.cache_row_stored)
    return {
        "latent": jnp.zeros(shape, _kv_storage_dtype(cfg.dtype)),
        "moe_counts": jnp.zeros((len(moe.COUNTS),), jnp.uint32),
    }


def paged_block_bytes(cfg: LongcatConfig, block_size: int) -> int:
    """Bytes one block of the pool holds over all attentions."""
    return 2 * cfg.num_layers * block_size * cfg.cache_row_stored * jnp.dtype(_kv_storage_dtype(cfg.dtype)).itemsize


_copy = jax.jit(lambda x: x + 0)


def routing_counts(pool: Dict):
    """A copy of the pool's routing counts that outlives the pool's donation
    to the next step: enqueued behind whatever writes the pool now, so reading
    it later waits for nothing that step would not have finished anyway."""
    return _copy(pool["moe_counts"])


# -- the forward pass ----------------------------------------------------------


def _forward_paged(params, tokens, positions, write_mask, block_tables, pool, cfg: LongcatConfig,
                   block_size: int, last=None):
    """``tokens`` (B, S) at ``positions`` (B, S); cache rows scattered into
    the pool (``write_mask`` clear: to the null block). S > 1 is a prefill of
    one prompt from position 0, which attends to its own rows per head; S == 1
    is a decode step, which gathers each sequence's table (``max_blocks x
    block_size`` latent rows) and attends in the absorbed form. ``last``: the
    one position whose logits are wanted (a prefill), else all. Returns
    (logits (B, S or 1, V), pool)."""
    b, s = tokens.shape
    t = b * s
    decode = s == 1
    if not decode and b != 1:
        raise ValueError("a prefill takes one prompt")
    mb = block_tables.shape[1]
    heads, eps, dn, dr, rkv = (cfg.num_attention_heads, cfg.rms_norm_eps, cfg.qk_nope_head_dim,
                               cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    att_scale = (dn + dr) ** -0.5
    x = params["embed"][tokens].reshape(t, -1)

    pidx = jnp.clip(positions // block_size, 0, mb - 1)
    slot = jnp.take_along_axis(block_tables, pidx, axis=1) * block_size + positions % block_size
    null_slot = jnp.arange(t, dtype=slot.dtype) % block_size
    write_slots = jnp.where(write_mask.reshape(-1), slot.reshape(-1), null_slot)
    live = write_mask.reshape(-1)
    write_blocks, write_rows = write_slots // block_size, write_slots % block_size
    if decode:
        lengths = jnp.where(write_mask[:, 0], positions[:, 0] + 1, 0)

    # The layers' tensors stay whole outside the loop, their two leading axes
    # (layer, which of the layer's two) merged, and a matmul reads its matrix
    # through one dynamic index. Handed to the scan as per-layer inputs, a
    # layer's (2, D, F) pair and its (experts, D, F) stack are copied out of
    # the stacked tensor before use: every weight read and written once more
    # a step (30 of 47 ms, PERF.md section 6, PR 29).
    merged = {k: v.reshape(-1, *v.shape[2:]) for k, v in params.items()
              if k not in _UNSTACKED and k not in _PER_LAYER and k != "hyper"}
    per_layer = {k: params[k] for k in _PER_LAYER}

    def mla(w, att_index, h, rows_pool):
        cq = rms_norm(h @ w("wqa"), w("qa_norm") * cfg.scale_q, eps)
        q = jnp.einsum("tr,kr->tk", cq, w("wqb")).reshape(b, s, heads, dn + dr)
        q_n, q_r = q[..., :dn], rope_interleaved(q[..., dn:], positions, cfg.rope_theta)
        kva = jnp.einsum("td,rd->tr", h, w("wkva")).reshape(b, s, -1)
        ckv = rms_norm(kva[..., :rkv], w("kva_norm") * cfg.scale_kv, eps)
        k_r = rope_interleaved(kva[..., rkv:], positions, cfg.rope_theta)
        new_rows = jnp.concatenate([ckv, k_r], axis=-1).astype(cfg.dtype)
        pad = cfg.cache_row_stored - cfg.cache_row
        bits = rows_pool.dtype != jnp.dtype(cfg.dtype)
        with jax.named_scope("latent_scatter"):
            flat = jnp.pad(new_rows.reshape(t, -1), ((0, 0), (0, pad)))
            if bits:
                flat = jax.lax.bitcast_convert_type(flat, rows_pool.dtype)
            rows_pool = rows_pool.at[att_index, write_blocks, write_rows].set(flat)
        wkvb = w("wkvb")
        if decode:
            with jax.named_scope("latent_gather"):
                rows = rows_pool[att_index, block_tables].reshape(b, mb * block_size, -1)[..., :cfg.cache_row]
                if bits:
                    rows = jax.lax.bitcast_convert_type(rows, cfg.dtype)
            with jax.named_scope("latent_attn"):
                q_l = jnp.einsum("bhn,hrn->bhr", q_n[:, 0], wkvb[..., :dn])
                o_l = latent_decode_attention(q_l, q_r[:, 0], rows, lengths, scale=att_scale)
                att = jnp.einsum("bhr,hrv->bhv", o_l, wkvb[..., dn:])
        else:
            with jax.named_scope("latent_attn"):
                kv = jnp.einsum("sr,hrk->shk", new_rows[0, :, :rkv], wkvb)
                att = latent_prefill_attention(q_n[0], q_r[0], kv[..., :dn], new_rows[0, :, rkv:], kv[..., dn:],
                                               scale=att_scale)
        return att.reshape(t, -1) @ w("wo"), rows_pool

    def ffn(w, h):
        return swiglu(h @ w("w_gate"), h @ w("w_up")) @ w("w_down")

    @jax.named_scope("block")
    def body(carry, layer_inputs):
        x, rows_pool, counts = carry
        lw, li = layer_inputs

        def of(which):  # the layer's first (0) or second (1) attention and MLP
            return lambda name: jax.lax.dynamic_index_in_dim(merged[name], 2 * li + which, keepdims=False)

        first, second = of(0), of(1)
        with jax.named_scope("mla0"):
            att, rows_pool = mla(first, 2 * li, rms_norm(x, first("in_norm"), eps), rows_pool)
        a = x + att
        u = rms_norm(a, first("post_norm"), eps)
        with jax.named_scope("moe"):
            m, routed = moe.expert_layer(
                {**lw, **{k: params[k] for k in _EXPERTS}}, u, layer=li, n_routed=cfg.n_routed_experts,
                top_k=cfg.moe_topk, scale=cfg.routed_scaling_factor, expert_offset=cfg.expert_offset, live=live)
        with jax.named_scope("ffn0"):
            x = a + ffn(first, u)
        with jax.named_scope("mla1"):
            att, rows_pool = mla(second, 2 * li + 1, rms_norm(x, second("in_norm"), eps), rows_pool)
        x = x + att
        with jax.named_scope("ffn1"):
            x = x + ffn(second, rms_norm(x, second("post_norm"), eps)) + m
        return (x, rows_pool, counts + routed if decode else counts), None

    (x, rows_pool, counts), _ = jax.lax.scan(
        body, (x, pool["latent"], pool["moe_counts"]), (per_layer, jnp.arange(cfg.num_layers)))
    with jax.named_scope("head"):
        x = x.reshape(b, s, -1)
        if last is not None:
            x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        x = rms_norm(x, params["final_norm"], eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"]).astype(jnp.float32)
    return logits, {"latent": rows_pool, "moe_counts": counts}


def make_paged_fns(cfg: LongcatConfig, *, block_size: int):
    """(prefill, decode_step, decode_step_greedy) with the signatures of
    ``generation.make_paged_fns``, the pool donated."""

    @functools.partial(jax.jit, donate_argnums=(3,))
    def prefill(params, tokens, block_table, pool, length):
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        logits, pool = _forward_paged(params, tokens, positions, positions < length, block_table, pool, cfg,
                                      block_size, last=length - 1)
        return logits[:, 0, :], pool

    def step(params, tokens, positions, block_tables, pool, active):
        logits, pool = _forward_paged(params, tokens[:, None], positions[:, None], active[:, None], block_tables,
                                      pool, cfg, block_size)
        return logits[:, 0, :], pool

    @functools.partial(jax.jit, donate_argnums=(4,))
    def decode_step(params, tokens, positions, block_tables, pool, active):
        return step(params, tokens, positions, block_tables, pool, active)

    @functools.partial(jax.jit, donate_argnums=(4,))
    def decode_step_greedy(params, tokens, positions, block_tables, pool, active):
        logits, pool = step(params, tokens, positions, block_tables, pool, active)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    return prefill, decode_step, decode_step_greedy
