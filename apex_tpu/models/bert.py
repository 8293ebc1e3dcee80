"""BERT-style bidirectional encoder (BASELINE config 4: BERT-base + FusedLAMB).

The reference has no BERT implementation — apex is the *utility* layer NVIDIA's
BERT recipes build on (FusedLAMB `apex/optimizers/fused_lamb.py`, fused
softmax `csrc/megatron/scaled_masked_softmax.h`, FusedLayerNorm, fused
dense). This model assembles exactly those apex_tpu pieces into the encoder
those recipes train, so the LAMB/fused-layer path has a realistic workload.

TPU notes: attention uses the Pallas flash kernel with padding expressed as
segment ids (packed-varlen FMHA analog, `apex/contrib/fmha/fmha.py:33-58`);
falls back to FusedScaleMaskSoftmax scores when ``use_flash=False``. All
matmuls accumulate fp32 on the MXU via ``preferred_element_type``. TP-capable
through Column/RowParallelLinear — runs unchanged at tp=1 and tp=k.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.utils.remat import resolve_remat_policy
from apex_tpu.ops import flash_attention
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.enums import AttnMaskType
from apex_tpu.transformer.functional import FusedScaleMaskSoftmax
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    mappings as tp_mappings)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30528          # padded to a multiple of 64 for the MXU
    max_seq_len: int = 512
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden_size: Optional[int] = None
    type_vocab_size: int = 2
    dtype: Any = jnp.bfloat16
    use_flash: bool = True
    remat_blocks: bool = False
    # see GPTConfig.remat_policy: None = full recompute, "dots" = save
    # matmul outputs, recompute the elementwise/LN chains in backward
    remat_policy: Optional[str] = None
    # Megatron-SP (see gpt.py): activations between layers are
    # sequence-sharded over the tensor axis
    sequence_parallel: bool = False
    # ``loss`` can fuse the tied LM-head matmul into the cross entropy
    # (``ops.lm_head_ce``; no [b, s, V] logits in HBM). Default False
    # for BERT by measurement, root-caused in round 5: the fused
    # backward pays a 4th full n·V·h dot (logit-tile recompute) while
    # the [n, V] bf16 logits traffic it saves is smaller and largely
    # hidden by XLA's scheduler — standalone at BERT-base shape the
    # fused kernel measures 20.8 ms vs 16.5-17.7 unfused (full step
    # r4: 121.3 unfused vs 123.1-126.1 fused). The attend dots already
    # run above step-average MXU efficiency (14.3% of step FLOPs in
    # 11.4% of step time), so this is structural, not tuning. Flip it
    # on for large-vocab / long-seq variants where the O(tokens + V)
    # memory bound is the point (GPT at V=32k/h=1024 measures the
    # other way at the FULL-STEP level — a whole-program residency
    # effect; see GPTConfig).
    fused_lm_head: bool = False

    @property
    def ffn(self):
        return self.ffn_hidden_size or 4 * self.hidden_size


def BertBase(**kw) -> "Bert":
    return Bert(BertConfig(**kw))


def BertLarge(**kw) -> "Bert":
    return Bert(BertConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw))


class BertSelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, pad_mask):
        """``pad_mask``: [b, s] bool, True = real token; None = no
        padding (skips the segment-id masking entirely — the flash
        kernel's segment path costs real VPU work per block, ~6% of a
        BERT-base step when fed an all-ones mask)."""
        cfg = self.cfg
        h = cfg.hidden_size
        tp = ps.get_tensor_model_parallel_world_size()
        heads_per = cfg.num_heads // tp
        head_dim = h // cfg.num_heads

        sp = ps.sequence_parallel_active(cfg.sequence_parallel)
        qkv = ColumnParallelLinear(
            input_size=h, output_size=3 * h, gather_output=False,
            sequence_parallel=sp, sequence_dim=1,
            name="qkv")(x)
        b, s, _ = qkv.shape
        qkv = qkv.reshape(b, s, heads_per, 3 * head_dim)
        q, k, v = jnp.split(qkv, 3, axis=-1)          # [b, s, hp, d]

        if cfg.use_flash:
            # padding → segment ids: real tokens segment 1, pads -1 (the
            # kernel zeroes their rows and excludes them as keys); no
            # pad_mask → plain unsegmented kernel (cheaper)
            sids = (None if pad_mask is None
                    else jnp.where(pad_mask, 1, -1).astype(jnp.int32))
            ctx = flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                segment_ids_q=sids, segment_ids_kv=sids,
                causal=False, scale=head_dim ** -0.5)
            ctx = ctx.transpose(0, 2, 1, 3).astype(cfg.dtype)
        else:
            scores = jnp.einsum("bshd,bthd->bhst", q, k,
                                preferred_element_type=jnp.float32)
            softmax = FusedScaleMaskSoftmax(
                input_in_bf16=cfg.dtype == jnp.bfloat16,
                attn_mask_type=AttnMaskType.padding,
                scale=head_dim ** -0.5)
            mask = (None if pad_mask is None
                    else ~pad_mask[:, None, None, :])  # True = masked out
            probs = softmax(scores.astype(cfg.dtype), mask)
            ctx = jnp.einsum("bhst,bthd->bshd", probs.astype(cfg.dtype), v,
                             preferred_element_type=jnp.float32
                             ).astype(cfg.dtype)
        ctx = ctx.reshape(b, s, heads_per * head_dim)
        return RowParallelLinear(
            input_size=h, output_size=h, input_is_parallel=True,
            sequence_parallel=sp, sequence_dim=1,
            name="proj")(ctx)


class BertLayer(nn.Module):
    """Post-LN transformer layer (original BERT residual order)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, pad_mask):
        cfg = self.cfg
        a = BertSelfAttention(cfg, name="attn")(x, pad_mask)
        x = FusedLayerNorm(normalized_shape=cfg.hidden_size, dtype=cfg.dtype,
                           name="ln1")(x + a)
        sp = ps.sequence_parallel_active(cfg.sequence_parallel)
        y = ColumnParallelLinear(
            input_size=cfg.hidden_size, output_size=cfg.ffn,
            gather_output=False, sequence_parallel=sp, sequence_dim=1,
            name="fc1")(x)
        y = jax.nn.gelu(y.astype(jnp.float32), approximate=True).astype(cfg.dtype)
        y = RowParallelLinear(
            input_size=cfg.ffn, output_size=cfg.hidden_size,
            input_is_parallel=True, sequence_parallel=sp, sequence_dim=1,
            name="fc2")(y)
        return FusedLayerNorm(normalized_shape=cfg.hidden_size, dtype=cfg.dtype,
                              name="ln2")(x + y)


class Bert(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, ids, pad_mask=None, type_ids=None,
                 return_hidden: bool = False):
        """Returns [b, s, V/tp] MLM logits (tied to the embedding shard);
        with ``return_hidden`` the pre-LM-head hidden states instead (the
        fused logits+CE path, see ``loss``)."""
        cfg = self.cfg  # pad_mask=None means "no padding" end-to-end
        wte = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size, embedding_dim=cfg.hidden_size,
            name="wte")
        x = wte(ids).astype(cfg.dtype)
        pos = self.param("wpe", nn.initializers.normal(0.02),
                         (cfg.max_seq_len, cfg.hidden_size), jnp.float32)
        x = x + pos[None, :ids.shape[1]].astype(cfg.dtype)
        if cfg.type_vocab_size:
            tok_type = self.param(
                "wtte", nn.initializers.normal(0.02),
                (cfg.type_vocab_size, cfg.hidden_size), jnp.float32)
            if type_ids is None:
                x = x + tok_type[0].astype(cfg.dtype)
            else:
                x = x + jnp.take(tok_type, type_ids, axis=0).astype(cfg.dtype)
        x = FusedLayerNorm(normalized_shape=cfg.hidden_size, dtype=cfg.dtype,
                           name="ln_emb")(x)
        sp = ps.sequence_parallel_active(cfg.sequence_parallel)
        if sp:
            tp = ps.get_tensor_model_parallel_world_size()
            if ids.shape[1] % tp:
                raise ValueError(
                    f"sequence_parallel requires seq len ({ids.shape[1]}) "
                    f"divisible by tp ({tp})")
            x = tp_mappings.scatter_to_sequence_parallel_region(
                x, ps.TENSOR_AXIS, 1)

        if cfg.remat_blocks:
            layer_cls = nn.remat(
                BertLayer, policy=resolve_remat_policy(cfg.remat_policy))
        else:
            layer_cls = BertLayer
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name=f"layer_{i}")(x, pad_mask)

        # MLM transform head (dense+gelu+LN), then tied decoder;
        # under SP the mlm_dense gathers the sequence back to full length
        x = ColumnParallelLinear(
            input_size=cfg.hidden_size, output_size=cfg.hidden_size,
            gather_output=True, sequence_parallel=sp, sequence_dim=1,
            name="mlm_dense")(x)
        x = jax.nn.gelu(x.astype(jnp.float32), approximate=True)
        x = FusedLayerNorm(normalized_shape=cfg.hidden_size, dtype=cfg.dtype,
                           name="mlm_ln")(x)
        if ps.get_tensor_model_parallel_world_size() > 1:
            # Megatron "f" before the tied output embedding: bwd
            # all-reduces the per-vocab-shard partial d(x) (see gpt.py)
            x = tp_mappings.copy_to_tensor_model_parallel_region(x)
        if return_hidden:
            return x
        return wte.attend(x)

    def loss(self, variables, ids, labels, pad_mask=None, type_ids=None,
             label_smoothing: float = 0.0, loss_mask=None):
        """Mean MLM cross entropy — by default via the fused LM-head+CE
        kernel (``ops.lm_head_ce``), so the [b, s, V] logits never hit
        HBM.

        ``loss_mask``: optional bool/0-1 [b, s] selecting the positions
        that count (MLM prediction positions / non-pad tokens); the mean
        normalizes by the mask total, so padded positions contribute
        neither loss nor gradient. Defaults to ``pad_mask`` when that is
        given (padding never trains), else every position."""
        from apex_tpu.ops.lm_head_ce import fused_lm_head_cross_entropy
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy)
        if self.cfg.fused_lm_head:
            hidden = self.apply(variables, ids, pad_mask, type_ids,
                                return_hidden=True)
            emb = variables["params"]["wte"]["embedding"]
            losses = fused_lm_head_cross_entropy(
                hidden, emb, labels, label_smoothing,
                axis_name=ps.TENSOR_AXIS)
        else:
            logits = self.apply(variables, ids, pad_mask, type_ids)
            losses = vocab_parallel_cross_entropy(
                logits, labels, label_smoothing)
        if loss_mask is None and pad_mask is not None:
            loss_mask = pad_mask
        if loss_mask is None:
            return jnp.mean(losses)
        w = loss_mask.astype(losses.dtype)
        return jnp.sum(losses * w) / jnp.maximum(jnp.sum(w), 1.0)

    @staticmethod
    def tensor_parallel_sharded_filter(path_names, leaf=None) -> bool:
        """True for params that are tp SHARDS (see
        ``GPT.tensor_parallel_sharded_filter``): qkv/fc1/mlm_dense
        kernel+bias (Column), proj/fc2 kernel (Row), the vocab-sharded
        embedding; ln*/wpe/wtte/row-bias leaves are replicated and count
        once in cross-rank norms. Delegates to the stack's shared
        classifier (BERT uses the conventional scope names)."""
        from apex_tpu.transformer.tensor_parallel.layers import (
            default_tp_sharded_filter)
        return default_tp_sharded_filter(path_names, leaf)

    @staticmethod
    def sequence_parallel_grad_filter(path_names, leaf) -> bool:
        """Params whose grads are per-tp-rank partials under SP: the
        in-block layernorms (operating on sequence-sharded activations)
        and the biases added after the sequence reduce-scatter.
        ``ln_emb``/``mlm_ln`` run on the full (replicated) sequence and
        must NOT be reduced."""
        del leaf
        names = [str(n).lower() for n in path_names]
        if any(n in ("ln1", "ln2") for n in names):
            return True
        return ("bias" in names
                and any(n in ("proj", "fc2") for n in names))
