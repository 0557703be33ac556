"""Model registry: name -> (constructor, loss, synthetic batch).

Glue between the spec layer (``run.container.args`` name a model) and
the runtime: the local runner, the benchmark harness, and
``__graft_entry__`` all instantiate models through here.  Synthetic
batches use deterministic numpy data (benchmarks measure compute, not
input pipelines; real data loaders plug in via ``runner``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .afmoe import AfmoeConfig, AfmoeModel
from .bert import BertConfig, BertModel
from .convnet import ConvNet
from .deepseek_v2 import DeepseekV2Config, DeepseekV2Model
from .gpt2 import GPT2Config, GPT2Model
from .jamba import JambaConfig, JambaModel
from .llama import LlamaConfig, LlamaModel
from .mlp import MLP
from .moe_gpt import MoEGPTConfig, MoEGPTModel
from .resnet import ResNet, ResNet50
from .t5 import T5Config, T5Model, shift_right
from .vit import ViTConfig, ViTModel


def softmax_xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


def _cfg_model(model_cls, base_cfg):
    """make_model for config-bearing models: keyword overrides patch
    CONFIG FIELDS (``dataclasses.replace``), so ``init_params(remat=True,
    remat_policy="dots_saveable")`` works uniformly — the MFU sweeps use
    this to walk remat/batch trade-offs without bespoke constructors."""
    def make(**kw):
        cfg = dataclasses.replace(base_cfg, **kw) if kw else base_cfg
        return model_cls(cfg)
    return make


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    make_model: Callable[..., Any]
    make_batch: Callable[[int], Dict[str, np.ndarray]]
    loss_fn: Callable[[Any], Callable]  # model -> loss(params, batch, rng)
    default_batch_size: int = 32
    # Analytic train-step FLOPs (fwd + bwd) as a function of batch size —
    # the MFU numerator.  XLA's compiled-module cost_analysis() is NOT a
    # substitute: it can't see inside pallas custom kernels (flash
    # attention reports zero flops), so benchmarks use these standard
    # closed forms
    # (6*N_matmul*tokens + attention term; 3x-forward for convnets).
    train_flops: Optional[Callable[[int], float]] = None
    # Analytic attention-only train FLOPs (the subset of train_flops
    # a pallas flash kernel computes), as ``f(batch, cfg)`` — the
    # cfg comes from the (possibly override-patched) model being
    # measured.  On TPU the flash custom call reports ZERO flops to
    # cost_analysis, so bench.py adds this term back when bridging
    # the XLA count to the analytic numerator
    # (bench.reconcile_flops; docs/SCALING.md "MFU accounting").
    attn_flops: Optional[Callable[[int, Any], float]] = None

    def init_params(self, batch_size: int = 2, seed: int = 0,
                    **overrides):
        model = self.make_model(**overrides)
        batch = self.make_batch(batch_size)
        rng = jax.random.PRNGKey(seed)
        variables = model.init(rng, batch["inputs"])
        return model, variables


def _image_batch(batch_size: int, hw: int, classes: int,
                 channels: int = 3) -> Dict[str, np.ndarray]:
    rng = np.random.RandomState(0)
    return {
        "inputs": rng.rand(batch_size, hw, hw, channels).astype("float32"),
        "labels": rng.randint(0, classes, size=(batch_size,)),
    }


def _token_batch(batch_size: int, seq: int,
                 vocab: int) -> Dict[str, np.ndarray]:
    rng = np.random.RandomState(0)
    return {"inputs": rng.randint(0, vocab, size=(batch_size, seq))}


def _classifier_loss(model):
    def loss(params, batch, rng):
        logits = model.apply(params, batch["inputs"], train=True,
                             rngs={"dropout": rng} if rng is not None
                             else None,
                             mutable=["batch_stats"]
                             if "batch_stats" in params else False)
        new_state = None
        if isinstance(logits, tuple):
            logits, new_state = logits
        l = softmax_xent(logits, batch["labels"])
        acc = (logits.argmax(-1) == batch["labels"]).mean()
        aux = {"accuracy": acc}
        if new_state:
            # TrainStep merges this back into state (BN running stats);
            # it never reaches the metrics dict.
            aux["__new_vars__"] = dict(new_state)
        return l, aux
    return loss


def _lm_loss(model):
    def loss(params, batch, rng):
        tokens = batch["inputs"]
        logits = model.apply(params, tokens, train=True)
        # Next-token prediction: shift by one.
        l = softmax_xent(logits[:, :-1], tokens[:, 1:])
        return l, {"perplexity": jnp.exp(l)}
    return loss


def _moe_lm_loss(model):
    """LM loss + weighted switch load-balance aux (the model returns
    ``(logits, aux)``)."""
    aux_weight = model.cfg.aux_weight

    def loss(params, batch, rng):
        tokens = batch["inputs"]
        logits, aux = model.apply(params, tokens, train=True)
        lm = softmax_xent(logits[:, :-1], tokens[:, 1:])
        return lm + aux_weight * aux, {"perplexity": jnp.exp(lm),
                                       "aux_loss": aux}
    return loss


def _seq2seq_loss(model):
    """Teacher-forced seq2seq xent: decoder inputs are the shift-right
    of ``labels`` (T5's pad-as-start convention); synthetic batches
    reuse ``inputs`` as ``labels`` (a denoising-style self-target).

    Optional batch keys (emitted by ``data.SpanCorruptionDataset``):
    ``enc_mask`` hides encoder padding; ``target_mask`` drops padded
    target positions from the mean."""
    def loss(params, batch, rng):
        src = batch["inputs"]
        tgt = batch.get("labels", src)
        dec_in = shift_right(jnp.asarray(tgt), model.cfg.pad_id)
        logits = model.apply(params, src, dec_in,
                             enc_mask=batch.get("enc_mask"),
                             train=True)
        mask = batch.get("target_mask")
        if mask is None:
            l = softmax_xent(logits, tgt)
        else:
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt)
            denom = jnp.maximum(mask.sum(), 1)
            l = jnp.where(mask.astype(bool), per_tok, 0.0).sum() / denom
        return l, {"perplexity": jnp.exp(l)}
    return loss


def _mlm_loss(model, mask_rate: float = 0.15, mask_id: int = 0):
    def loss(params, batch, rng):
        tokens = batch["inputs"]
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        mask = jax.random.bernoulli(rng, mask_rate, tokens.shape)
        inputs = jnp.where(mask, mask_id, tokens)
        logits = model.apply(params, inputs, train=True)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens)
        denom = jnp.maximum(mask.sum(), 1)
        l = jnp.where(mask, per_tok, 0.0).sum() / denom
        return l, {"masked_tokens": mask.sum()}
    return loss


def _transformer_train_flops(batch: int, *, layers: int, hidden: int,
                             seq: int, head_params: int,
                             intermediate: Optional[int] = None,
                             extra_matmul_params: int = 0,
                             causal: bool = False) -> float:
    """Standard analytic train FLOPs (fwd + 2x bwd) for a transformer.

    dense = 6 * N_matmul * tokens  (N_matmul: qkv/o/mlp kernels + head;
    embedding *lookups* are gathers, not matmuls, and are excluded).
    attention = 12 * layers * tokens * seq * hidden  (the two S^2 matmuls,
    fwd 4*S*h per token per layer, x3 for training), halved for causal
    models — the standard MFU convention of counting only the needed
    (lower-triangle) work; the kernel may compute more than that when its
    block size doesn't let it skip fully-masked blocks.
    """
    inter = 4 * hidden if intermediate is None else intermediate
    n_matmul = layers * (4 * hidden * hidden + 2 * hidden * inter) \
        + head_params + extra_matmul_params
    tokens = batch * seq
    dense = 6.0 * n_matmul * tokens
    attn = 12.0 * layers * tokens * seq * hidden
    if causal:
        attn /= 2.0
    return dense + attn


def _attn_only_flops(*, seq: int, causal: bool):
    """The attention term of _transformer_train_flops, alone.

    Takes the MODEL CONFIG at call time (not baked into the closure)
    so bench overrides that change num_layers/hidden_size — the MFU
    sweeps do exactly this — keep the term consistent with the model
    actually being measured."""
    def flops(b: int, cfg) -> float:
        attn = (12.0 * cfg.num_layers * (b * seq) * seq
                * cfg.hidden_size)
        return attn / 2.0 if causal else attn
    return flops


def _gpt2_train_flops(cfg: GPT2Config, seq: int):
    return lambda b: _transformer_train_flops(
        b, layers=cfg.num_layers, hidden=cfg.hidden_size, seq=seq,
        head_params=cfg.hidden_size * cfg.vocab_size, causal=True)


def _bert_train_flops(cfg: BertConfig, seq: int):
    return lambda b: _transformer_train_flops(
        b, layers=cfg.num_layers, hidden=cfg.hidden_size, seq=seq,
        head_params=cfg.hidden_size * cfg.vocab_size,
        intermediate=cfg.intermediate_size)


def _moe_train_flops(cfg: MoEGPTConfig, seq: int):
    # Top-1 switch routing: each token runs ONE expert MLP + the router.
    return lambda b: _transformer_train_flops(
        b, layers=cfg.num_layers, hidden=cfg.hidden_size, seq=seq,
        head_params=cfg.hidden_size * cfg.vocab_size,
        extra_matmul_params=cfg.num_layers * cfg.hidden_size
        * cfg.num_experts,
        causal=True)


def _llama_train_flops(cfg: LlamaConfig, seq: int):
    # SwiGLU = 3 MLP matmuls (gate/up/down); GQA shrinks only the k/v
    # projections; attention score/PV FLOPs follow the QUERY head count.
    h, hd = cfg.hidden_size, cfg.head_dim
    per_layer = (2 * h * h                       # q_proj + o_proj
                 + 2 * h * cfg.num_kv_heads * hd  # k_proj + v_proj
                 + 3 * h * cfg.intermediate_size)
    n_matmul = cfg.num_layers * per_layer + h * cfg.vocab_size

    def flops(b: int) -> float:
        tokens = b * seq
        return (6.0 * n_matmul * tokens
                + 12.0 * cfg.num_layers * tokens * seq * h / 2.0)
    return flops


def _t5_train_flops(cfg: T5Config, seq: int):
    """Encoder + decoder + cross-attention closed form.  The attention
    term follows the zoo convention (12 * L * tokens * S * width, where
    width is T5's decoupled inner dim), halved for the causal decoder
    self-attention; cross-attention is full (T_dec x S_enc)."""
    d, inner, ff = cfg.d_model, cfg.inner_dim, cfg.d_ff
    ff_mats = 3 if cfg.feed_forward == "gated-gelu" else 2
    enc_layer = 4 * d * inner + ff_mats * d * ff
    dec_layer = 8 * d * inner + ff_mats * d * ff
    n_matmul = (cfg.num_layers * enc_layer
                + cfg.num_decoder_layers * dec_layer
                + d * cfg.vocab_size)

    def flops(b: int) -> float:
        tokens = b * seq
        dense = 6.0 * n_matmul * tokens
        attn = 12.0 * tokens * seq * inner * (
            cfg.num_layers                       # encoder, bidirectional
            + cfg.num_decoder_layers / 2.0       # decoder self, causal
            + cfg.num_decoder_layers)            # cross, full
        return dense + attn
    return flops


def _vit_train_flops(cfg: "ViTConfig"):
    patches = cfg.num_patches + 1  # + [CLS]
    patch_dim = cfg.patch_size * cfg.patch_size * 3
    return lambda b: _transformer_train_flops(
        b, layers=cfg.num_layers, hidden=cfg.hidden_size, seq=patches,
        head_params=cfg.hidden_size * cfg.num_classes,
        intermediate=cfg.intermediate_size,
        extra_matmul_params=patch_dim * cfg.hidden_size)


# ResNet-50 at 224x224: ~4.1 GMACs fwd (8.2 GFLOPs); training ~= 3x fwd
# (bwd is two matmul-sized passes).  Matches the XLA compiled-module
# count (23.9 GFLOPs/img) within 3%.
_RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 8.2e9


_REGISTRY: Dict[str, ModelSpec] = {}


def _register(spec: ModelSpec):
    _REGISTRY[spec.name] = spec
    return spec


_register(ModelSpec(
    name="mlp",
    make_model=lambda **kw: MLP(**kw),
    make_batch=lambda b: _image_batch(b, 28, 10, channels=1),
    loss_fn=_classifier_loss,
    default_batch_size=64,
))

_register(ModelSpec(
    name="convnet",
    make_model=lambda **kw: ConvNet(**kw),
    make_batch=lambda b: _image_batch(b, 32, 10),
    loss_fn=_classifier_loss,
    default_batch_size=128,
))

_register(ModelSpec(
    name="resnet50",
    make_model=lambda **kw: ResNet50(**kw),
    make_batch=lambda b: _image_batch(b, 224, 1000),
    loss_fn=_classifier_loss,
    default_batch_size=128,
    train_flops=lambda b: b * _RESNET50_TRAIN_FLOPS_PER_IMG,
))

_register(ModelSpec(
    name="resnet50-tiny",  # CI-sized stand-in, same code path
    make_model=lambda **kw: ResNet(
        stage_sizes=(1, 1, 1, 1), width=8, num_classes=10, **kw),
    make_batch=lambda b: _image_batch(b, 32, 10),
    loss_fn=_classifier_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="bert-base",
    make_model=_cfg_model(BertModel, BertConfig.base()),
    make_batch=lambda b: _token_batch(b, 512, BertConfig.base().vocab_size),
    loss_fn=_mlm_loss,
    default_batch_size=32,
    train_flops=_bert_train_flops(BertConfig.base(), 512),
    attn_flops=_attn_only_flops(seq=512, causal=False),
))

_register(ModelSpec(
    name="bert-tiny",
    make_model=_cfg_model(BertModel, BertConfig.tiny()),
    make_batch=lambda b: _token_batch(b, 64, BertConfig.tiny().vocab_size),
    loss_fn=_mlm_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="gpt2-medium",
    make_model=_cfg_model(GPT2Model, GPT2Config.medium()),
    make_batch=lambda b: _token_batch(b, 1024,
                                      GPT2Config.medium().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
    train_flops=_gpt2_train_flops(GPT2Config.medium(), 1024),
    attn_flops=_attn_only_flops(seq=1024, causal=True),
))

_register(ModelSpec(
    name="gpt2-small",
    make_model=_cfg_model(GPT2Model, GPT2Config.small()),
    make_batch=lambda b: _token_batch(b, 1024,
                                      GPT2Config.small().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
    train_flops=_gpt2_train_flops(GPT2Config.small(), 1024),
    attn_flops=_attn_only_flops(seq=1024, causal=True),
))

_register(ModelSpec(
    name="gpt2-mini",  # serving-benchmark-sized (GPT2Config.mini)
    make_model=_cfg_model(GPT2Model, GPT2Config.mini()),
    make_batch=lambda b: _token_batch(b, 256,
                                      GPT2Config.mini().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="gpt2-tiny",
    make_model=_cfg_model(GPT2Model, GPT2Config.tiny()),
    make_batch=lambda b: _token_batch(b, 64, GPT2Config.tiny().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="tinyllama-1.1b",
    make_model=_cfg_model(LlamaModel, LlamaConfig.tinyllama()),
    make_batch=lambda b: _token_batch(b, 2048,
                                      LlamaConfig.tinyllama().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=4,
    train_flops=_llama_train_flops(LlamaConfig.tinyllama(), 2048),
    attn_flops=_attn_only_flops(seq=2048, causal=True),
))

_register(ModelSpec(
    name="mistral-tiny",  # Llama + sliding-window local attention + GQA
    # _cfg_model so serving overrides (kv_cache_int8, kv_cache_ring)
    # patch CONFIG fields like every other config-bearing model.
    make_model=_cfg_model(LlamaModel, LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, max_position=256,
        sliding_window=31)),
    make_batch=lambda b: _token_batch(b, 128, 512),
    loss_fn=_lm_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="llama-tiny",
    make_model=_cfg_model(LlamaModel, LlamaConfig.tiny()),
    make_batch=lambda b: _token_batch(b, 64, LlamaConfig.tiny().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="t5-small",
    make_model=_cfg_model(T5Model, T5Config.small()),
    make_batch=lambda b: _token_batch(b, 512, T5Config.small().vocab_size),
    loss_fn=_seq2seq_loss,
    default_batch_size=16,
    train_flops=_t5_train_flops(T5Config.small(), 512),
))

_register(ModelSpec(
    name="t5-tiny",
    make_model=_cfg_model(T5Model, T5Config.tiny()),
    make_batch=lambda b: _token_batch(b, 64, T5Config.tiny().vocab_size),
    loss_fn=_seq2seq_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="vit-base",
    make_model=_cfg_model(ViTModel, ViTConfig.base()),
    make_batch=lambda b: _image_batch(b, 224, 1000),
    loss_fn=_classifier_loss,
    default_batch_size=64,
    train_flops=_vit_train_flops(ViTConfig.base()),
))

_register(ModelSpec(
    name="vit-tiny",
    make_model=_cfg_model(ViTModel, ViTConfig.tiny()),
    make_batch=lambda b: _image_batch(b, 32, 10),
    loss_fn=_classifier_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="moe-gpt-small",
    make_model=_cfg_model(MoEGPTModel, MoEGPTConfig.small()),
    make_batch=lambda b: _token_batch(b, 1024,
                                      MoEGPTConfig.small().vocab_size),
    loss_fn=_moe_lm_loss,
    default_batch_size=8,
    train_flops=_moe_train_flops(MoEGPTConfig.small(), 1024),
))

_register(ModelSpec(
    name="moe-gpt-tiny",
    make_model=_cfg_model(MoEGPTModel, MoEGPTConfig.tiny()),
    make_batch=lambda b: _token_batch(b, 64,
                                      MoEGPTConfig.tiny().vocab_size),
    loss_fn=_moe_lm_loss,
    default_batch_size=8,
))

_register(ModelSpec(
    name="afmoe-tiny",
    make_model=_cfg_model(AfmoeModel, AfmoeConfig.tiny()),
    make_batch=lambda b: _token_batch(b, 16,
                                      AfmoeConfig.tiny().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
))

# Served only (perfbench cell trinity-serve-mixed): the batch is what
# ``init_params`` traces the parameter shapes with, nothing trains it.
_register(ModelSpec(
    name="trinity-large-ep8",
    make_model=_cfg_model(AfmoeModel, AfmoeConfig.trinity_large_ep8()),
    make_batch=lambda b: _token_batch(
        b, 8, AfmoeConfig.trinity_large_ep8().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=1,
))

_register(ModelSpec(
    name="jamba-tiny",
    make_model=_cfg_model(JambaModel, JambaConfig.tiny()),
    make_batch=lambda b: _token_batch(b, 16,
                                      JambaConfig.tiny().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
))

# Served only (perfbench cell jamba2-serve-chat), uncut.
_register(ModelSpec(
    name="jamba2-3b",
    make_model=_cfg_model(JambaModel, JambaConfig.jamba2_3b()),
    make_batch=lambda b: _token_batch(
        b, 8, JambaConfig.jamba2_3b().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=1,
))

_register(ModelSpec(
    name="deepseek-v2-tiny",
    make_model=_cfg_model(DeepseekV2Model, DeepseekV2Config.tiny()),
    make_batch=lambda b: _token_batch(
        b, 16, DeepseekV2Config.tiny().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=8,
))

# Served only (perfbench cell dsv2lite-serve-longdoc): stage 0 of four.
_register(ModelSpec(
    name="deepseek-v2-lite-stage0",
    make_model=_cfg_model(DeepseekV2Model,
                          DeepseekV2Config.v2_lite_stage0()),
    make_batch=lambda b: _token_batch(
        b, 8, DeepseekV2Config.v2_lite_stage0().vocab_size),
    loss_fn=_lm_loss,
    default_batch_size=1,
))


def get_model(name: str) -> ModelSpec:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_models():
    return sorted(_REGISTRY)
