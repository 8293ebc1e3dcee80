"""The ``qwen3_next`` family: how a Qwen3-Next configuration (``model_type:
qwen3_next``: gated-delta-rule linear attention in three layers of four, a
gated full attention in the fourth, a softmax top-k renormalised expert
layer beside a gated shared expert) becomes a train step and a reference
check. After ``families/mellum.py``, whose path it takes: the program's own
``amp.initialize`` -> ``cast_params`` -> ``opt.init`` ->
``amp.make_train_step(has_aux=True)``, one executable compiled ahead of
time, inspected and stepped, the newest steps' ``aux`` kept on the device for
the readers of the traced steps. ``amp.initialize`` is given the model's
``keep_fp32`` (``A_log`` and ``dt_bias`` stay float32).

A configuration file of this family holds every key of the model's
``config.json`` twice: at its top level AS IT IS RUN, where the keys its
``reduced`` lists (``num_hidden_layers``, ``num_experts``, ``vocab_size``)
say what THIS chip holds of the deployment the file describes, and untouched
under ``published``; ``held`` says which experts, rows and layers. The router
keeps its published width; the reference is given the same share.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Optional

from benchmarks.harness import counts_qwen3_next as counts
from benchmarks.reference import qwen3_next as ref

from . import _amp

_L0, _L3 = "layer_0", "layer_3"
#: the leaves whose gradients the check compares: each passes through one of
#: the backwards this family's model brought. Layer 0's gated-delta leaves
#: through the scan's backward kernel and its in-chunk solve (``A_log`` and
#: ``dt_bias`` through the decays alone, ``ba`` through decays and writing
#: strengths, ``qkvz`` and ``conv`` through q, k, v and the gate of the norm,
#: ``out`` behind it); ``W_q`` (query and gate) and ``W_k`` of full layer 3
#: through the banded dq and dk/dv kernels at head size 256 with dK/dV
#: summed over a group of 8; the router through the combine's weights, the
#: routed experts through the grouped matmul's dw and dx, the shared expert
#: and its gate through ``moe_dropless``'s differentiated shared path
GRAD_LEAVES = (
    (_L0, "gdn", "qkvz"), (_L0, "gdn", "ba"), (_L0, "gdn", "A_log"),
    (_L0, "gdn", "dt_bias"), (_L0, "gdn", "conv"), (_L0, "gdn", "out"),
    (_L3, "attn", "q"), (_L3, "attn", "k"),
    (_L0, "moe", "router"), (_L0, "moe", "experts", "gate_up"),
    (_L0, "moe", "experts", "down"), (_L0, "moe", "shared", "gate"),
    (_L0, "moe", "shared", "up"), (_L0, "moe", "shared", "down"),
    (_L0, "moe", "shared", "out_gate"))


@dataclasses.dataclass
class Qwen3NextProgram(_amp.TrainProgram):
    info: Optional[dict] = None
    #: the ``aux`` of the newest steps, on the device, oldest first
    aux_log: Any = None


def model_config(config: dict):
    import jax.numpy as jnp
    from apex_tpu.models.qwen3_next import Qwen3NextConfig
    pub, held, assumed = (config[k] for k in ("published", "held", "assumed"))
    assert assumed["dtype"] == "bfloat16", assumed["dtype"]
    assert pub["rope_scaling"] is None and not pub["mlp_only_layers"] \
        and pub["decoder_sparse_step"] == 1, "not what this family builds"
    return Qwen3NextConfig(
        vocab_size=config["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=pub["num_attention_heads"],
        num_kv_heads=pub["num_key_value_heads"], head_dim=pub["head_dim"],
        linear_num_key_heads=pub["linear_num_key_heads"],
        linear_num_value_heads=pub["linear_num_value_heads"],
        linear_key_head_dim=pub["linear_key_head_dim"],
        linear_value_head_dim=pub["linear_value_head_dim"],
        moe_intermediate_size=pub["moe_intermediate_size"],
        shared_expert_intermediate_size=pub[
            "shared_expert_intermediate_size"],
        n_routed_experts=pub["num_experts"],
        num_experts_per_tok=pub["num_experts_per_tok"],
        full_attention_interval=pub["full_attention_interval"],
        linear_conv_kernel_dim=pub["linear_conv_kernel_dim"],
        partial_rotary_factor=pub["partial_rotary_factor"],
        first_expert=held["first_expert"],
        n_local_experts=held["local_experts"],
        rms_norm_eps=pub["rms_norm_eps"],
        rope_theta=float(pub["rope_theta"]),
        dtype=jnp.bfloat16, init_std=assumed["initializer_std"])


def reference_sizes(config: dict) -> dict:
    """The published keys the reference reads."""
    return {k: config["published"][k] for k in ref.KEYS}


def _leaf(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def compare(cfg, sizes: dict, params, ids) -> dict:
    """The program ``cfg`` on ``params`` against the plain reference, for
    token ids ``[n, s]`` (``families/mellum.py:compare``'s three readings):
    the forward's logits (the reference summing over the experts the PROGRAM
    chose), the choice itself, and the gradients of the summed next-token
    loss for :data:`GRAD_LEAVES`. Readings only; the limits are the
    caller's."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import qwen3_next as qn

    n, s = ids.shape
    layers, tokens = cfg.num_layers, n * s
    labels = jnp.roll(ids, -1, axis=1)
    rel = _amp.rel_err_fn()
    got, aux = jax.jit(lambda p, i: qn.forward(cfg, p, i))(params, ids)
    mine = aux["moe_idx"].reshape(layers, n, s, -1)
    kw = dict(first_expert=cfg.first_expert, forced=mine)
    want, theirs, z = ref.forward(params, ids, sizes, routing=True, **kw)
    err, finite = rel(got, want)
    del got, want
    E = z.shape[-1]
    off, tie = jax.jit(lambda a, b, z: (
        (jnp.sort(a, -1) != jnp.sort(b, -1)).any(-1).reshape(-1),
        ref.tie_distance(z.reshape(-1, E), a.reshape(-1, a.shape[-1]))
    ))(mine, theirs, z)
    differ = int(off.sum())
    tie = float(jnp.max(jnp.where(off, tie, 0.0)))
    del theirs, z
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: qn.loss(cfg, p, ids, labels)[0] * tokens))(params)
    grads = {path: _leaf(grads, path) for path in GRAD_LEAVES}
    want = jax.jit(lambda p, i, l, f: ref.grads(
        p, i, l, sizes, GRAD_LEAVES, first_expert=cfg.first_expert,
        forced=f, reduce=jnp.sum))(params, ids, labels, mine)
    grad_err = {}
    for path in GRAD_LEAVES:
        e, ok = rel(grads[path], want[path])
        grad_err["/".join(path)] = float(e)
        finite = finite & ok
    del grads, want
    loss_ref = jax.jit(lambda p, i, l, f: ref.loss(
        p, i, l, sizes, reduce=jnp.sum, first_expert=cfg.first_expert,
        forced=f))(params, ids, labels, mine)
    return {"logit_rel_err": float(err),
            "routing_rows_compared": layers * tokens,
            "routing_rows_that_differ": differ,
            "routing_tie_distance": tie,
            "grad_rel_err": grad_err,
            "loss_sum": float(loss), "loss_sum_reference": float(loss_ref),
            "finite": bool(finite)}


def build_train(config: dict, traffic: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.models import qwen3_next as qn
    from apex_tpu.ops import gated_delta
    from apex_tpu.transformer import parallel_state as ps

    assert traffic.get("entry", "amp") == "amp", traffic.get("entry")
    cfg = model_config(config)
    assert list(qn.FP32_LEAVES) == config["assumed"]["fp32_leaves"]
    sizes = reference_sizes(config)
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    vocab = cfg.vocab_size
    ps.destroy_model_parallel()
    amp_model, opt = amp.initialize(
        lambda p, ids: qn.forward(cfg, p, ids)[0],
        _amp.make_optimizer(traffic["optimizer"]),
        opt_level=traffic["opt_level"], verbosity=0,
        keep_fp32_predicate=qn.keep_fp32)

    def init_state(key):
        params = amp_model.cast_params(qn.init_params(cfg, key))
        return params, opt.init(params), \
            opt._amp_stash.loss_scalers[0].state

    def make_ring(key):
        ids = jax.random.randint(key, (int(traffic["ring"]), batch, seq), 0,
                                 vocab, jnp.int32)
        return ids, jnp.roll(ids, -1, axis=2)       # next-token labels

    t = [time.perf_counter()]

    def lap():
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    k_init, k_ring, k_check = jax.random.split(jax.random.PRNGKey(seed), 3)
    state = jax.block_until_ready(jax.jit(init_state)(k_init))
    timings = {"init_s": lap()}
    ring = jax.jit(make_ring)(k_ring)
    batches = [tuple(a[i] for a in ring)
               for i in range(int(traffic["ring"]))]
    timings["ring_s"] = lap()

    step = amp.make_train_step(
        lambda p, ids, labels: qn.loss(cfg, p, ids, labels), opt,
        has_aux=True)
    # ONE compile: the executable that is inspected is the one stepped
    lowered = step._jitted.lower(False, *state, *batches[0])
    timings["trace_and_lower_s"] = lap()
    compiled = lowered.compile()
    timings["compile_or_cache_load_s"] = lap()

    aux_log = collections.deque(
        maxlen=2 * max(int(traffic.get("trace_steps", 4)),
                       int(traffic["fetch_every"])))

    def run_step(state, batch):
        p, o, s, loss, aux = compiled(*state, *batch)
        aux_log.append(aux)
        return (p, o, s), loss

    n_check, s_check = traffic["check"]["shape"]

    def check():
        """Forward, routing and backward at the published widths and the
        timed length (:func:`compare`), held to the configuration file's
        three limits; the file has each limit's reason."""
        ids = jax.random.randint(k_check, (n_check, s_check), 0, vocab,
                                 jnp.int32)
        got = compare(cfg, sizes, state[0], ids)
        tie_limit = float(config["routing_tie_distance"])
        grad_limit = float(config["grad_tolerance"])
        ok = got["routing_tie_distance"] <= tie_limit \
            and max(got["grad_rel_err"].values()) <= grad_limit
        err = got["logit_rel_err"]
        return {"what": f"{n_check} x {s_check} tokens at the published "
                        f"widths: forward logits (bf16, kernels, the timed "
                        f"forward) vs the plain float32 reference (the "
                        f"recurrence token by token) summed over the "
                        f"experts the program chose; the choice differs "
                        f"from the reference's own only within "
                        f"routing_tie_distance of a tie of its logits; "
                        f"gradients of the summed loss (the program's "
                        f"value_and_grad) vs jax.grad of the reference's "
                        f"for GRAD_LEAVES within grad_tolerance; a miss of "
                        f"either raises rel_err to 1",
                "rel_err": err if ok else max(1.0, err), **got,
                "routing_tie_distance_limit": tie_limit,
                "grad_tolerance": grad_limit,
                "tolerance": float(config["logit_tolerance"])}

    kinds = collections.Counter(cfg.layer_types)
    fpt = 3.0 * counts.forward_flops_per_token(
        config["published"], list(cfg.layer_types), seq,
        chunk=gated_delta.CHUNK, experts_held=cfg.local_experts,
        vocab_held=vocab)
    return Qwen3NextProgram(
        state=state, step=run_step, batches=batches,
        tokens_per_step=batch * seq,
        applied_steps=lambda st: int(st[1].groups[0].step),
        loss_scale=lambda st: float(st[2].loss_scale), check=check,
        memory=_amp.memory_dict(compiled), n_classes=vocab,
        flops_per_token=fpt,
        # "banded", as the mellum family's: the accepted flash rooflines
        # count one causal shape for every layer; this one has one such
        # layer in four, with grouped heads and no window layer
        attention={"kind": "banded", "batch": batch, "heads": cfg.num_heads,
                   "kv_heads": cfg.num_kv_heads, "seq": seq,
                   "head_dim": cfg.head_dim, "window": None,
                   "window_kernel": r"^apx_flash_attention_window_",
                   "full_kernel": r"^apx_flash_attention_(fwd|bwd)",
                   "window_layers": 0, "full_layers": kinds[qn.FULL]},
        notes={"setup_timings": timings},
        info={"moe": {"kernel": r"^apx_moe_grouped_matmul",
                      "layers": cfg.num_layers,
                      "experts_held": cfg.local_experts,
                      "hidden": cfg.hidden_size,
                      "inter": cfg.moe_intermediate_size},
              "gdn": {"fwd_kernel": r"^apx_gdn_(chunk|scan)_fwd",
                      "bwd_kernel": r"^apx_gdn_(chunk|scan)_bwd",
                      "layers": kinds[qn.LINEAR], "batch": batch,
                      "heads": cfg.linear_num_value_heads, "seq": seq,
                      "chunk": gated_delta.CHUNK,
                      "d_k": cfg.linear_key_head_dim,
                      "d_v": cfg.linear_value_head_dim},
              "recompute": "block",
              "parameters": int(sum(x.size
                                    for x in jax.tree.leaves(state[0])))},
        aux_log=aux_log)
