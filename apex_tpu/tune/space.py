"""Per-kernel config-space generation for the autotuner.

Enumerates legal block grids from static shape/dtype information alone,
pruned by the shared VMEM-envelope model (:mod:`apex_tpu.tune.vmem`)
so illegal configs never reach a compile. The enumeration is
deterministic: candidates come out in a fixed order (coarsest blocks
first), which makes sweep tie-breaking reproducible.
"""

from __future__ import annotations

from typing import Optional

from apex_tpu.tune import vmem

# power-of-two block menu shared by both flash phases; Mosaic wants the
# trailing dims (8, 128)-aligned and every real sweep to date has only
# ever ranked powers of two
_FLASH_BLOCKS = (1024, 512, 256, 128)
_CE_BLOCK_T = (1024, 512, 256, 128)
_CE_BLOCK_V = (8192, 4096, 2048, 1024, 512, 256, 128)
# KV-cache page sizes for the serve decode kernel: the page is the
# kernel's block (one page of one head per program), AND the pool's
# allocation granule — smaller pages waste less tail capacity per
# sequence, larger pages cut program count. 8-sublane aligned.
_DECODE_BLOCKS = (512, 256, 128, 64, 32, 16)
# row blocks for the fused LayerNorm kernel pair (fwd+bwd share the
# knob): bigger blocks amortize per-program overhead, smaller ones trade
# VMEM for h — the envelope prunes per shape
_LN_BLOCKS = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
# flat-shard chunks for the multi-tensor optimizer update; must stay a
# multiple of one fp32 VMEM tile (8 sublanes x 128 lanes = 1024 elts)
# because the kernel views the flat buffer as [rows, 128]
_MTU_BLOCKS = (262144, 131072, 65536, 32768, 16384, 8192, 4096, 2048,
               1024)
# contraction/output tiles for the fp8 dequant-matmul: block_k rides
# both x's lane dim and the e4m3 weight's sublane dim (fp8 tiling wants
# 32-sublane multiples — every 128 qualifies), block_n the output lanes
_FP8MM_BLOCKS_K = (512, 256, 128)
_FP8MM_BLOCKS_N = (2048, 1024, 512, 256, 128)


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def _clip_menu(menu, limit: int):
    """Menu entries no larger than the (power-of-two-rounded) limit —
    blocks clamp to the sequence inside the kernels, so anything past
    the padded extent is a duplicate of the clamped config."""
    cap = _pow2_ceil(limit)
    out = [m for m in menu if m <= cap]
    return out or [menu[-1]]


def flash_attention_space(*, sq: int, sk: int, d: int, itemsize: int = 2,
                          phase: str = "fwd", bias: bool = False,
                          dropout: bool = False,
                          segments: bool = False) -> list[dict]:
    """Legal ``{"block_q", "block_k"}`` candidates for one flash phase.

    ``phase`` is ``"fwd"`` or ``"bwd"`` — the two are tuned
    independently (their measured optima differ: the r5 retune landed
    (1024, 1024) forward / (512, 512) backward at the causal GPT shape).
    """
    if phase not in ("fwd", "bwd"):
        raise ValueError(f"phase must be 'fwd' or 'bwd', got {phase!r}")
    kernel = f"flash_attention_{phase}"
    out = []
    for bq in _clip_menu(_FLASH_BLOCKS, sq):
        for bk in _clip_menu(_FLASH_BLOCKS, sk):
            if vmem.fits(kernel, block_q=bq, block_k=bk, d=d,
                         itemsize=itemsize, bias=bias, dropout=dropout,
                         segments=segments):
                out.append({"block_q": bq, "block_k": bk})
    return out


def lm_head_ce_space(*, n: int, v: int, h: int,
                     itemsize: int = 2) -> list[dict]:
    """Legal ``{"block_t", "block_v"}`` candidates for the fused
    LM-head CE kernels (forward and backward share the tiling knobs)."""
    out = []
    for bt in _clip_menu(_CE_BLOCK_T, n):
        for bv in _clip_menu(_CE_BLOCK_V, v):
            if vmem.fits("lm_head_ce", block_t=bt, block_v=bv, h=h,
                         itemsize=itemsize):
                out.append({"block_t": bt, "block_v": bv})
    return out


def decode_attention_space(*, s: int, d: int, group: int = 1,
                           itemsize: int = 2) -> list[dict]:
    """Legal ``{"block_kv"}`` (KV-cache page size) candidates for the
    paged decode kernel. ``s`` is the context length the sweep measures
    at — pages are clipped to it like flash blocks clip to the
    sequence."""
    out = []
    for bkv in _clip_menu(_DECODE_BLOCKS, max(s, _DECODE_BLOCKS[-1])):
        if vmem.fits("decode_attention", block_kv=bkv, d=d, group=group,
                     itemsize=itemsize):
            out.append({"block_kv": bkv})
    return out


def fused_layer_norm_space(*, n: int, h: int,
                           itemsize: int = 2) -> list[dict]:
    """Legal ``{"block_r"}`` row-block candidates for the fused LN
    kernel pair (forward and single-pass backward share the knob)."""
    out = []
    for br in _clip_menu(_LN_BLOCKS, n):
        if vmem.fits("fused_layer_norm", block_r=br, h=h,
                     itemsize=itemsize):
            out.append({"block_r": br})
    return out


def xentropy_space(*, n: int, v: int, itemsize: int = 2) -> list[dict]:
    """Legal ``{"block_t", "block_v"}`` candidates for the fused
    softmax-CE kernels (fwd/bwd share the tiling, like lm_head_ce)."""
    out = []
    for bt in _clip_menu(_CE_BLOCK_T, n):
        for bv in _clip_menu(_CE_BLOCK_V, v):
            if vmem.fits("xentropy", block_t=bt, block_v=bv,
                         itemsize=itemsize):
                out.append({"block_t": bt, "block_v": bv})
    return out


def multi_tensor_update_space(*, n: int, itemsize: int = 4) -> list[dict]:
    """Legal ``{"block_n"}`` flat-shard chunk candidates for the fused
    multi-tensor optimizer update."""
    out = []
    for bn in _clip_menu(_MTU_BLOCKS, max(n, _MTU_BLOCKS[-1])):
        if vmem.fits("multi_tensor_update", block_n=bn,
                     itemsize=itemsize):
            out.append({"block_n": bn})
    return out


def fp8_matmul_space(*, m: int, k: int, n: int,
                     itemsize: int = 2) -> list[dict]:
    """Legal ``{"block_k", "block_n"}`` candidates for the fused fp8
    dequant-matmul (serve weight-streaming)."""
    out = []
    for bk in _clip_menu(_FP8MM_BLOCKS_K, k):
        for bn in _clip_menu(_FP8MM_BLOCKS_N, n):
            if vmem.fits("fp8_matmul", block_k=bk, block_n=bn,
                         group=max(m, 1), itemsize=itemsize):
                out.append({"block_k": bk, "block_n": bn})
    return out


def config_space(kernel: str, shape: dict,
                 flags: Optional[dict] = None) -> list[dict]:
    """Dispatch on the cache's kernel naming: ``flash_attention_fwd``,
    ``flash_attention_bwd``, ``lm_head_ce``, ``decode_attention``.
    ``shape``/``flags`` use the same field names the cache key is built
    from."""
    flags = flags or {}
    if kernel == "decode_attention":
        return decode_attention_space(
            s=shape["s"], d=shape["d"], group=shape.get("group", 1),
            itemsize=shape.get("itemsize", 2))
    if kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        return flash_attention_space(
            sq=shape["sq"], sk=shape["sk"], d=shape["d"],
            itemsize=shape.get("itemsize", 2),
            phase=kernel.rsplit("_", 1)[1],
            bias=bool(flags.get("bias")), dropout=bool(flags.get("dropout")),
            segments=bool(flags.get("segments")))
    if kernel == "lm_head_ce":
        return lm_head_ce_space(n=shape["n"], v=shape["v"], h=shape["h"],
                                itemsize=shape.get("itemsize", 2))
    if kernel == "fused_layer_norm":
        return fused_layer_norm_space(n=shape["n"], h=shape["h"],
                                      itemsize=shape.get("itemsize", 2))
    if kernel == "xentropy":
        return xentropy_space(n=shape["n"], v=shape["v"],
                              itemsize=shape.get("itemsize", 2))
    if kernel == "multi_tensor_update":
        return multi_tensor_update_space(
            n=shape["n"], itemsize=shape.get("itemsize", 4))
    if kernel == "fp8_matmul":
        return fp8_matmul_space(
            m=shape.get("m", 8), k=shape["k"], n=shape["n"],
            itemsize=shape.get("itemsize", 2))
    raise ValueError(f"unknown kernel {kernel!r}; known: {vmem.KERNELS}")
