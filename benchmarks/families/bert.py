"""The ``bert`` family: BERT masked-LM pre-training through the same
``amp.initialize`` -> ``make_train_step`` path as ``gpt``, with the
optimizer the traffic file names (FusedLAMB in the first cell).

The batch is ``(ids, labels, loss_mask)``: ids uniform over the published
vocabulary, labels another uniform draw (the "original" tokens at the masked
positions), and ``mask_share`` of the positions in ``loss_mask``, all from
the seed; no padding, one segment.
"""

from __future__ import annotations

from benchmarks.harness import flops as flops_mod
from benchmarks.reference import bert as ref

from . import _amp


def logit_tolerance(config: dict) -> float:
    """The configuration's own ``logit_tolerance``, twice the largest error
    read on the chip in bf16, by ``families/gpt.py:logit_tolerance``'s
    argument. The program (bf16, flash kernel, tanh GELU, LayerNorm eps
    1e-5) is held to a float32 reference that follows the source (erf GELU,
    eps 1e-12): the tanh GELU is within 1e-3 of the erf one in absolute
    value, under one bf16 eps of the activations it feeds, and post-LN
    re-normalises the stream in every block."""
    return float(config["logit_tolerance"])


def model_config(sizes: dict, **kw):
    import jax.numpy as jnp
    from apex_tpu.models.bert import BertConfig
    assert sizes["dtype"] == "bfloat16", sizes["dtype"]
    return BertConfig(
        vocab_size=sizes["padded_vocab_size"],
        max_seq_len=sizes["max_position_embeddings"],
        hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        ffn_hidden_size=sizes["intermediate_size"],
        type_vocab_size=sizes["type_vocab_size"], dtype=jnp.bfloat16, **kw)


def build_train(config: dict, traffic: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.bert import Bert

    if traffic.get("entry", "amp") != "amp":
        raise ValueError(f"bert family: unknown train entry "
                         f"{traffic['entry']!r}")
    sizes = config["sizes"]
    model = Bert(model_config(sizes))
    b, s, ring = (int(traffic[k]) for k in ("batch", "seq", "ring"))
    share = float(traffic["mask_share"])

    def make_ring(key):
        k1, k2, k3 = jax.random.split(key, 3)
        ids = jax.random.randint(k1, (ring, b, s), 0, sizes["vocab_size"],
                                 jnp.int32)
        labels = jax.random.randint(k2, (ring, b, s), 0,
                                    sizes["vocab_size"], jnp.int32)
        mask = jax.random.uniform(k3, (ring, b, s)) < share
        return ids, labels, mask

    n, cs = traffic.get("check", {}).get("shape", [2, 256])
    n_head = sizes["num_attention_heads"]
    fpt = flops_mod.train_flops_per_token(
        flops_mod.bert_forward_flops_per_token(sizes, s))
    return _amp.amp_train_program(
        model=model,
        loss_fn=lambda p, i, l, m: model.loss({"params": p}, i, l,
                                              loss_mask=m),
        init_args=(jnp.zeros((1, s), jnp.int32),),
        make_ring=make_ring, traffic=traffic, seed=seed,
        forward=lambda p, ids: model.apply({"params": p}, ids),
        reference_forward=lambda p, ids: ref.forward(
            p, ids, n_head=n_head, eps=sizes["layer_norm_eps"]),
        check_ids=lambda key: jax.random.randint(
            key, (n, cs), 0, sizes["vocab_size"], jnp.int32),
        tol=logit_tolerance(config), n_classes=sizes["padded_vocab_size"],
        flops_per_token=fpt,
        attention={"kind": "flash", "kernel": r"^apx_flash_attention",
                   "batch": b, "heads": n_head, "seq": s,
                   "head_dim": sizes["hidden_size"] // n_head,
                   "causal": False, "layers": sizes["num_hidden_layers"]})
