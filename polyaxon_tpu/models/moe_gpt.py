"""MoE-GPT — switch-transformer decoder wired to expert parallelism.

The reference orchestrates MoE workloads only as user code inside its
job kinds (SURVEY.md §2.12: no parallelism implemented in-repo); here
the model family is first-class: a GPT-2-style decoder whose FFN is a
top-1 (switch) mixture of experts running through
``parallel.moe.moe_layer`` — experts sharded over the mesh's ``ep``
axis, tokens dispatched via ICI all-to-all.  With no ambient mesh (or
``ep == 1``) the same routing math runs dense (identical semantics at
``ep=1``; per-source-rank capacity is the only EP-specific behavior),
so ``model.init`` and single-device tests need no mesh.

Aux (load-balance) loss flows through the ``nn.scan`` carry — no
mutable collections — and the model returns ``(logits, aux)``; the
registry's ``_moe_lm_loss`` adds ``aux_weight * aux`` to the LM loss.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.constraints import BATCH, constrain, current_mesh
from ..parallel.moe import moe_layer, top1_dispatch
from .attention import dot_product_attention
from .kv_cache import attend_kv_cache
from .scan_stack import LayerScanBody, scan_layers


@dataclass(frozen=True)
class MoEGPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_experts: int = 8
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    max_position: int = 1024
    layer_norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    # Serve-time option: store the decode KV cache as int8 with
    # per-(token, head) bf16 scales (kv_cache.py) — halves the
    # KV bytes each decoded token streams from HBM.
    kv_cache_int8: bool = False

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @staticmethod
    def small() -> "MoEGPTConfig":
        return MoEGPTConfig()  # gpt2-small dims x 8 experts (~0.6B total)

    @staticmethod
    def tiny() -> "MoEGPTConfig":
        return MoEGPTConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                            num_heads=4, num_experts=4, max_position=128)


def _switch_ffn_decode(flat, router_w, w1, w2, activation):
    """Per-token top-1 FFN for decode: gather ONLY the routed expert's
    weights per token instead of running every expert (the dense
    dispatch path costs num_experts x the FLOPs and, under an
    ep-sharded mesh, an all-gather of every expert's weights per
    generated token).  Identical math to drop-free dispatch: out =
    p_e * w2_e(act(w1_e x))."""
    logits = flat.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)                      # [T]
    gate = jnp.take_along_axis(probs, idx[:, None], 1)    # [T, 1]
    w1_t = jnp.take(w1.astype(jnp.float32), idx, axis=0)  # [T, d, f]
    w2_t = jnp.take(w2.astype(jnp.float32), idx, axis=0)  # [T, f, d]
    h = activation(jnp.einsum("td,tdf->tf", flat.astype(jnp.float32),
                              w1_t))
    out = jnp.einsum("tf,tfd->td", h, w2_t) * gate
    # Aux (load-balance) loss is a training signal; decode returns 0.
    return out, jnp.zeros((), jnp.float32)


def _switch_ffn_prefill(flat, router_w, w1, w2, activation):
    """Exact drop-free top-1 FFN for chunked prefill, scatter-bucketed.

    The dense dispatch with drop-free capacity C = T builds a [T, E, C]
    one-hot, making prefill O(T^2 E) in memory AND FLOPs — a 2048-token
    prompt with 8 experts materialized ~134 MB of dispatch tensor per
    layer (ADVICE r2).  Instead: position-in-expert from an O(T E)
    cumsum, tokens scattered into [E, T, d] buckets, batched expert
    matmuls, gathered back by (expert, position).  Identical math to
    the per-token decode path; the remaining overhead is the bucketed
    expert matmul's empty slots (inherent to static-shape drop-free
    routing on TPU).
    """
    t, d = flat.shape
    e = router_w.shape[-1]
    x32 = flat.astype(jnp.float32)
    logits = x32 @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(probs, axis=-1)                      # [T]
    gate = jnp.take_along_axis(probs, idx[:, None], 1)    # [T, 1]
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0),
                              idx[:, None], 1)[:, 0] - 1  # [T]
    buckets = jnp.zeros((e, t, d), jnp.float32).at[idx, pos].set(x32)
    h = activation(jnp.einsum("ecd,edf->ecf", buckets,
                              w1.astype(jnp.float32)))
    out_b = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32))
    out = out_b[idx, pos] * gate
    return out, jnp.zeros((), jnp.float32)


def _switch_ffn_dense(flat, router_w, w1, w2, capacity: int, activation):
    """The ep=1 semantics of ``moe_layer`` without collectives (used for
    init and meshless runs; also the single-device reference in tests)."""
    logits = flat.astype(jnp.float32) @ router_w.astype(jnp.float32)
    dispatch, combine, aux = top1_dispatch(logits, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           flat.astype(jnp.float32))
    h = activation(jnp.einsum("ecd,edf->ecf", expert_in,
                              w1.astype(jnp.float32)))
    h = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32))
    out = jnp.einsum("tec,ecd->td", combine, h)
    return out, aux


class MoEMlp(nn.Module):
    """Switch FFN: expert-parallel when an ``ep>1`` mesh is ambient."""

    cfg: MoEGPTConfig

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.cfg
        d, e, f = cfg.hidden_size, cfg.num_experts, cfg.intermediate_size
        init = nn.initializers.normal(0.02)
        router_w = self.param("router", init, (d, e), jnp.float32)
        w1 = self.param("experts_w1", init, (e, d, f), jnp.float32)
        w2 = self.param("experts_w2", init, (e, f, d), jnp.float32)

        mesh = current_mesh()
        if not decode and mesh is not None and \
                mesh.shape.get("ep", 1) > 1:
            out, aux = moe_layer(
                x, router_w, w1, w2, mesh,
                capacity_factor=cfg.capacity_factor,
                activation=nn.gelu)
            return out.astype(cfg.dtype), aux
        b, s, _ = x.shape
        if decode and s == 1:
            # Single-token step: gather only the routed expert's
            # weights (the dense path would run every expert).
            out, aux = _switch_ffn_decode(x.reshape(b * s, d), router_w,
                                          w1, w2, nn.gelu)
        elif decode:
            # Chunked prefill: per-token weight GATHERS would
            # materialize [T, d, f] copies (~GBs at real sizes), and
            # the dense dispatch at drop-free capacity is O(T^2 E) —
            # scatter buckets give exact top-1 at O(E T d).
            out, aux = _switch_ffn_prefill(x.reshape(b * s, d), router_w,
                                           w1, w2, nn.gelu)
        else:
            capacity = max(1, int(cfg.capacity_factor * b * s / e))
            out, aux = _switch_ffn_dense(x.reshape(b * s, d), router_w,
                                         w1, w2, capacity, nn.gelu)
        return out.reshape(x.shape).astype(cfg.dtype), aux


class MoEBlock(nn.Module):
    """Pre-LN decoder block: dense attention + switch-MoE FFN."""

    cfg: MoEGPTConfig

    @nn.compact
    def __call__(self, x, decode: bool = False, layer=None):
        # ``layer``: this block's index where the scanned stack
        # carries the whole KV cache (scan_stack.LayerScanBody).
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_heads

        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln1")(x).astype(cfg.dtype)
        qkv = nn.Dense(3 * cfg.hidden_size, dtype=cfg.dtype,
                       name="qkv")(h)
        qkv = constrain(qkv, BATCH, None, "tp")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = h.shape[:-1] + (cfg.num_heads, head_dim)
        q, k, v = (t.reshape(shape) for t in (q, k, v))
        if decode:
            # KV-cache step (single token or chunked prefill); the
            # switch FFN below picks its kernel by chunk size.
            a = attend_kv_cache(
                self, lambda k, v, mask, _: dot_product_attention(
                    q, k, v, mask=mask),
                k, v, cfg.max_position, quantize=cfg.kv_cache_int8,
                layer=layer)
        else:
            a = dot_product_attention(q, k, v, causal=True)
        a = a.reshape(h.shape)
        a = constrain(a, BATCH, None, "tp")
        x = x + nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name="o_proj")(a)
        x = constrain(x, BATCH, None, None)

        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln2")(x).astype(cfg.dtype)
        ffn, aux = MoEMlp(cfg, name="moe")(h, decode=decode)
        x = x + ffn
        return constrain(x, BATCH, None, None), aux


class _ScanMoEBlock(LayerScanBody):
    """nn.scan body: carries (x, aux_sum) so the load-balance loss flows
    out of the rolled layer stack without mutable collections.
    ``decode`` rides as an nn.broadcast input, ``layer`` comes with a
    carried KV cache (see scan_stack)."""

    cfg: MoEGPTConfig

    @nn.compact
    def __call__(self, carry, decode=None, layer=None):
        x, aux_sum = carry
        if decode:
            x, aux = MoEBlock(self.cfg, name="block")(
                x, decode=True, layer=layer)
            return (x, aux_sum + aux), None
        cls = nn.remat(MoEBlock, prevent_cse=False) if self.cfg.remat \
            else MoEBlock
        x, aux = cls(self.cfg, name="block")(x)
        return (x, aux_sum + aux), None


class MoEGPTModel(nn.Module):
    """``__call__(input_ids) -> (logits, aux)``; ``aux`` is the mean
    switch load-balance loss over layers (weighted by the loss fn)."""

    cfg: MoEGPTConfig

    def setup(self):
        cfg = self.cfg
        self.wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                            dtype=cfg.dtype, name="wte")
        self.wpe = nn.Embed(cfg.max_position, cfg.hidden_size,
                            dtype=cfg.dtype, name="wpe")
        self.h = scan_layers(_ScanMoEBlock, cfg.num_layers, cfg,
                             name="h")
        self.ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                 dtype=jnp.float32, name="ln_f")

    def __call__(self, input_ids, *, train: bool = False,
                 decode: bool = False, decode_position=None,
                 last_only: bool = False):
        if decode and decode_position is None:
            raise ValueError(
                "MoE-GPT decode needs decode_position (learned wpe; "
                "generate() supplies it)")
        x = constrain(self.wte(input_ids), BATCH, None, None)
        pos = jnp.arange(input_ids.shape[-1])
        if decode:
            pos = pos + decode_position
        x = x + self.wpe(pos)
        x = constrain(x, BATCH, None, None)
        (x, aux), _ = self.h.run((x, jnp.zeros((), jnp.float32)),
                                 decode)
        if last_only:  # prefill: one row of logits, not [B, P, V]
            x = x[:, -1:]
        x = self.ln_f(x)
        logits = self.wte.attend(x.astype(self.cfg.dtype))
        logits = constrain(logits.astype(jnp.float32), BATCH, None, "tp")
        return logits, aux / self.cfg.num_layers
