"""The ``longcat`` family: how a LongCat-Flash configuration (two latent
attentions and two dense feed-forwards a layer with the expert layer on a
shortcut; a softmax top-k router over real and zero-compute slots) becomes
a serve engine and a reference check. Imports the program under test
(``apex_tpu``) and nothing of the harness's arithmetic.

A configuration file of this family holds every key of the model's
``config.json`` twice: at its top level AS IT IS RUN, where the keys its
``reduced`` lists (``num_layers``, ``n_routed_experts``, ``vocab_size``)
say what THIS chip holds of the deployment the file describes, and
untouched under ``published``. Beside them ``assumed`` and a group ``held``
for what the share needs and ``config.json`` has no key for: the
``first_expert`` held. The router keeps its published width; the reference
is given the same share.

Serving only: at 16 bytes a parameter one layer's share of training state
alone is 19.9 GB (PERF.md section 4).
"""

from __future__ import annotations

from benchmarks.reference import longcat as ref


def model_config(config: dict, max_seq_len: int):
    import jax.numpy as jnp
    from apex_tpu.models.longcat import LongcatConfig
    pub, held, assumed = (config[k] for k in ("published", "held", "assumed"))
    assert assumed["dtype"] == "bfloat16", assumed["dtype"]
    assert pub["zero_expert_type"] == "identity", pub["zero_expert_type"]
    return LongcatConfig(
        vocab_size=config["vocab_size"], hidden_size=pub["hidden_size"],
        num_layers=config["num_layers"],
        num_heads=pub["num_attention_heads"],
        q_lora_rank=pub["q_lora_rank"], kv_lora_rank=pub["kv_lora_rank"],
        qk_nope_head_dim=pub["qk_nope_head_dim"],
        qk_rope_head_dim=pub["qk_rope_head_dim"],
        v_head_dim=pub["v_head_dim"],
        ffn_hidden_size=pub["ffn_hidden_size"],
        expert_ffn_hidden_size=pub["expert_ffn_hidden_size"],
        n_routed_experts=pub["n_routed_experts"],
        zero_expert_num=pub["zero_expert_num"], moe_topk=pub["moe_topk"],
        routed_scaling_factor=pub["routed_scaling_factor"],
        mla_scale_q_lora=pub["mla_scale_q_lora"],
        mla_scale_kv_lora=pub["mla_scale_kv_lora"],
        first_expert=held["first_expert"],
        n_local_experts=config["n_routed_experts"],
        rms_norm_eps=pub["rms_norm_eps"], rope_theta=pub["rope_theta"],
        max_seq_len=max_seq_len, dtype=jnp.bfloat16,
        init_std=assumed["initializer_std"])


def build_serve(config: dict, traffic: dict, seed: int):
    """``ServeEngine`` with default impls over weights made on the device
    from ``--seed`` in bf16, the chip's share as the file's top level and
    its ``held`` say."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu import serve
    from apex_tpu.models import longcat as lc
    from apex_tpu.serve.longcat import LongcatServed
    from apex_tpu.transformer import moe_dropless
    from apex_tpu.ops import grouped_matmul as gmm
    from apex_tpu.transformer import parallel_state as ps
    from benchmarks.harness import loadgen
    from benchmarks.harness.serve import ServeProgram

    pub, held = config["published"], config["held"]
    eng_kw = dict(traffic["engine"])
    ps.destroy_model_parallel()
    cfg = model_config(config, eng_kw["max_seq_len"])
    params = jax.jit(lambda key: lc.init_params(cfg, key))(
        jax.random.PRNGKey(seed))
    # the pool holds the mix's worst case: every slot at its longest prompt
    # + longest output, +1 for the null page
    page = int(eng_kw["page_size"])
    longest = (loadgen.longest(traffic["prompt_len"])
               + loadgen.longest(traffic["output_len"]))
    num_pages = eng_kw["max_batch"] * -(-longest // page) + 1
    eng = serve.ServeEngine(LongcatServed(cfg), params, num_pages=num_pages,
                            **eng_kw)

    n_check, n_new = traffic.get("check", {}).get("shape", [4, 5])
    pad_to = -(-(eng_kw["max_prompt_len"] + n_new) // 8) * 8
    vocab = config["vocab_size"]

    def check():
        """Prefill then ``n_new - 1`` decode steps through the two latent
        leaves a layer (expanded, then absorbed attention) vs the
        reference's full forward over the same tokens, a request at a time
        so that the float32 reference fits beside the engine. Two limits
        (the configuration file has both with their reasons): the logits,
        with the reference summing over the slots the PROGRAM chose, zero
        slots included (its arithmetic against float32's), and the choice
        itself, which may differ from the reference's own only at a
        near-tie of the reference's corrected scores."""
        rng = np.random.RandomState(seed + 104729)
        prompts = [rng.randint(
            0, vocab, loadgen.draw_length(rng, traffic["prompt_len"])
        ).tolist() for _ in range(n_check)]
        eng.record_logits = True
        sids = [eng.add_request(p, n_new) for p in prompts]
        eng.run()
        eng.record_logits = False
        errs, finite, differ, compared, tie = [], True, 0, 0, 0.0
        for sid, p in zip(sids, prompts):
            seq = eng.seqs[sid].tokens
            assert len(seq) == len(p) + n_new, (len(seq), len(p), n_new)
            n_fed = len(seq) - 1                # the last token is never fed
            toks = np.zeros((1, pad_to), np.int32)
            toks[0, :len(seq)] = seq
            rows = np.asarray([[len(p) + j - 1 for j in range(n_new)]],
                              np.int32)         # the row that predicts p+j
            aux = eng.aux_log[sid]
            mine = np.zeros((pad_to,) + aux[len(p)]["moe_idx"].shape[1:],
                            np.int32)           # [pad_to, layers, k]
            mine[:len(p)] = aux[len(p)]["moe_idx"][:len(p)]
            for j in range(1, n_new):
                mine[len(p) + j - 1] = aux[len(p) + j]["moe_idx"]
            mine = mine.transpose(1, 0, 2)      # [layers, pad_to, k]
            want, chosen, cor = ref.forward(
                params, jnp.asarray(toks), pub, rows=jnp.asarray(rows),
                first_expert=held["first_expert"], routing=True,
                forced=jnp.asarray(mine[:, None]))
            want = np.asarray(want)[0]
            got = np.stack([eng.logits_log[sid][len(p) + j]
                            for j in range(n_new)])
            finite &= bool(np.isfinite(got).all() and np.isfinite(want).all())
            errs.append(float(np.max(np.abs(got - want))
                              / np.max(np.abs(want))))
            theirs = np.sort(np.asarray(chosen)[:, 0, :n_fed], -1)
            off = (np.sort(mine[:, :n_fed], -1) != theirs).any(-1)
            compared += off.size
            differ += int(off.sum())
            if off.any():
                need = ref.tie_distance(np.asarray(cor)[:, 0, :n_fed][off],
                                        mine[:, :n_fed][off])
                tie = max(tie, float(need.max()))
        eng.logits_log.clear()
        eng.aux_log.clear()
        tie_limit = float(config["routing_tie_distance"])
        routing_ok = tie <= tie_limit
        return {"what": f"{n_check} requests: prefill (expanded attention) "
                        f"+ {n_new - 1} decode steps (absorbed, through "
                        f"two latent leaves a layer) vs the plain float32 "
                        f"reference's full forward at the published widths, "
                        f"summed over the slots the program chose; the "
                        f"choice differs from the reference's own only "
                        f"within routing_tie_distance (a relative move of "
                        f"the corrected scores) of a tie, else rel_err is "
                        f"raised to 1",
                "rel_err": max(errs) if routing_ok else max(1.0, *errs),
                "rel_err_by_request": errs,
                "prompt_lens": [len(p) for p in prompts],
                "routing_rows_compared": compared,
                "routing_rows_that_differ": differ,
                "routing_tie_distance": tie,
                "routing_tie_distance_limit": tie_limit,
                "finite": finite,
                "tolerance": float(config["logit_tolerance"])}

    k, nl = cfg.moe_topk, cfg.local_experts
    bm = moe_dropless.BLOCK_M_DECODE
    decode_rows = bm * gmm.num_tiles(nl, bm,
                                     eng.max_batch * min(k, nl))
    return ServeProgram(
        engine=eng, vocab=vocab, check=check,
        attention={"kind": "mla_decode",
                   "kernel": r"^apx_mla_decode_attention",
                   "heads": cfg.num_heads, "latent_dim": cfg.latent_dim,
                   "value_dim": cfg.kv_lora_rank,
                   "layers": eng.ccfg.num_layers},      # the cache's leaves
        programs={"prefill": r"^jit_prefill$"},
        info={"paged_impl": eng.paged_impl,
              "attention_impl": eng.attention_impl,
              "page_size": eng.ccfg.page_size,
              "num_pages": eng.ccfg.num_pages,
              "pool_bytes": eng.ccfg.pool_bytes(),
              "latent_row_lanes": eng.ccfg.width,
              "latent_leaves": eng.ccfg.num_layers,
              "max_batch": eng.max_batch,
              "moe": {"kernel": "apx_moe_grouped_matmul",
                      "layers": cfg.num_layers, "experts_held": nl,
                      "router_slots": cfg.router_slots, "top_k": k,
                      "hidden": cfg.hidden_size,
                      "inter": cfg.expert_ffn_hidden_size,
                      "decode_rows": decode_rows},
              "weight_bytes": int(sum(
                  x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params)))})
