"""Kernels that compile for the chip, kept as tests.

The TPU's compiler is installed even where no TPU is attached, and it
compiles for a chip that is *described* (on-chip-measurement guide §2.3).
Every case here lowers a main-path Pallas kernel with ``interpret=False``
at the widths ``chip_smoke.py`` runs (GPT 12L / h1024 / 16 heads / V32768,
b8 s1024) on ``ShapeDtypeStruct``s placed on a described v5e, and requires a
``tpu_custom_call`` in the compiled text: what Mosaic would refuse on the
chip — a misaligned slice, too much VMEM — fails here, at no chip time,
where interpret-mode tests pass. A compile that passes is not a chip run.

``interpret=False`` is passed explicitly: left to themselves the kernels ask
``jax.default_backend()``, see the CPU and interpret (zero custom calls).
The two ``slow`` cases compile whole programs that take no such argument —
the train step ``chip_smoke.py`` builds and the ``ServeEngine`` programs —
and steer that rule by monkeypatch, here in the test.
"""

import dataclasses
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu import _compat
from apex_tpu.amp import fp8 as fp8_mod
from apex_tpu.lint.jaxpr_checks import iter_eqns
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.paged_attention import (_DECODE_BUFFER_BYTES,
                                          paged_decode_attention,
                                          paged_kv_write_rows)
from apex_tpu.ops.fp8_matmul import fp8_dequant_matmul
from apex_tpu.ops.fused_ce import softmax_cross_entropy_with_smoothing
from apex_tpu.ops.layer_norm import fused_layer_norm_affine
from apex_tpu.ops.lm_head_ce import fused_lm_head_cross_entropy
from apex_tpu.serve import cache as cache_mod
from apex_tpu.zero.fused_update import fused_shard_update

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
B, H, D, HID, V = 8, 16, 64, 1024, 32768


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host (four chips), or skip the module."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu / no TPU compiler on this machine
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")


@pytest.fixture(scope="module")
def chip(topo):
    """One chip of that host."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def chip_matmul_precision():
    """conftest pins ``jax_default_matmul_precision="highest"`` for CPU
    accuracy; the kernels' in-VMEM dots inherit it, and Mosaic refuses a
    bf16 dot at fp32 contract precision ("Bad lhs type"). Compile as a
    program on the chip would: at the default precision."""
    with jax.default_matmul_precision("default"):
        yield


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _place(chip, tree):
    return jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), tree)


def _sum_grad(fn, argnums):
    """fwd+bwd of ``fn`` through a scalar."""
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(F32)), argnums=argnums)


def _flash(s, b):
    def build(chip):
        q = _sds(chip, (b, H, s, D), BF16)
        fn = _sum_grad(functools.partial(flash_attention, causal=True,
                                         interpret=False), (0, 1, 2))
        return fn, (q, q, q)
    return build


def _lm_head_ce(chip):
    fn = _sum_grad(lambda x, e, t: fused_lm_head_cross_entropy(
        x, e, t, interpret=False), (0, 1))
    return fn, (_sds(chip, (B * 1024, HID), BF16),
                _sds(chip, (V, HID), BF16), _sds(chip, (B * 1024,), I32))


def _paged_decode(fp8):
    def build(chip):
        page = cache_mod.resolve_page_size(
            kv_heads=H, head_dim=D, context_len=1024, dtype=BF16, fp8=fp8,
            batch=B)
        pool = _sds(chip, (H, 64, page, 2 * D),
                    fp8_mod.E4M3 if fp8 else BF16)
        args = [_sds(chip, (B, H, 1, D), BF16), pool,
                _sds(chip, (B, 1024 // page), I32), _sds(chip, (B,), I32)]
        if fp8:
            scales = _sds(chip, (H, 64), F32)
            return (lambda q, kv, bt, sl, ks, vs: paged_decode_attention(
                q, kv, bt, sl, k_scales=ks, v_scales=vs,
                interpret=False)), args + [scales, scales]
        return functools.partial(paged_decode_attention,
                                 interpret=False), args
    return build


def _paged_decode_at(b, kv, num_pages, page, slots):
    """The bf16 decode kernel at MHA on ``b`` rows of ``kv`` heads of 64."""
    def build(chip):
        return (functools.partial(paged_decode_attention, interpret=False),
                (_sds(chip, (b, kv, 1, D), BF16),
                 _sds(chip, (kv, num_pages, page, 2 * D), BF16),
                 _sds(chip, (b, slots), I32), _sds(chip, (b,), I32)))
    return build


#: the benchmark's serve cell: 64 rows, 16 heads, one layer's leaf of 385
#: pages of 128 tokens, 8 slots a row
_paged_decode_cell3 = _paged_decode_at(64, H, 385, 128, 8)


def _kv_write(fp8):
    """One layer's decode write and prompt write into a small pool,
    through the aliased Pallas writes the engine uses on the chip."""
    def build(chip):
        ccfg = cache_mod.CacheConfig(num_layers=2, kv_heads=H, head_dim=D,
                                     num_pages=64, page_size=128,
                                     dtype=BF16, fp8=fp8)
        state = jax.eval_shape(lambda: cache_mod.init_cache(ccfg))

        def fn(state, page_ids, slots, k_new, table, length, k_seq):
            state = cache_mod.write_token(
                ccfg, state, 0, page_ids, slots, k_new, k_new,
                impl="kernel", interpret=False)
            return cache_mod.write_prompt(
                ccfg, state, 1, table, length, k_seq, k_seq, impl="kernel",
                interpret=False)
        return fn, (_place(chip, state), _sds(chip, (B,), I32),
                    _sds(chip, (B,), I32), _sds(chip, (B, H, D), BF16),
                    _sds(chip, (4,), I32), _sds(chip, (), I32),
                    _sds(chip, (512, H, D), BF16))
    return build


def _layer_norm(chip):
    fn = _sum_grad(lambda x, w, b: fused_layer_norm_affine(
        x, w, b, (HID,), out_dtype=BF16, block_r=256, interpret=False),
        (0, 1, 2))
    return fn, (_sds(chip, (B * 1024, HID), BF16),
                _sds(chip, (HID,), F32), _sds(chip, (HID,), F32))


def _fused_ce(chip):
    fn = _sum_grad(lambda lg, t: softmax_cross_entropy_with_smoothing(
        lg, t, block_t=256, block_v=2048, interpret=False), (0,))
    return fn, (_sds(chip, (B * 1024, V), BF16),
                _sds(chip, (B * 1024,), I32))


def _fp8_matmul(chip):
    """The four block linears of an h1024 layer, decode batch of 8."""
    shapes = ((HID, 3 * HID), (HID, HID), (HID, 4 * HID), (4 * HID, HID))

    def fn(x1, x4, s, *qs):
        return [fp8_dequant_matmul(x4 if q.shape[0] == 4 * HID else x1, q,
                                   s, block_k=512, block_n=512,
                                   interpret=False) for q in qs]
    return fn, (_sds(chip, (B, HID), BF16), _sds(chip, (B, 4 * HID), BF16),
                _sds(chip, (), F32),
                *[_sds(chip, kn, fp8_mod.E4M3) for kn in shapes])


def _shard_update(chip):
    n = 12 * HID * HID        # one layer's block linears, flat, fp32
    fn = functools.partial(
        fused_shard_update, kind="adam", lr=1e-3, betas=(0.9, 0.999),
        eps=1e-8, weight_decay=0.0, adam_w_mode=True, bias_correction=True,
        block_n=64 * 1024, interpret=False)
    x = _sds(chip, (n,), F32)
    return fn, (x, x, x, x, _sds(chip, (), I32))


def _flash_fwd_d192(chip):
    """Prefill's expanded latent attention: one padded 1,024-token prompt,
    64 heads of 128 + 64 for q and k, 192 for v, unpadded."""
    q = _sds(chip, (1, 64, 1024, 192), BF16)
    return functools.partial(flash_attention, causal=True, scale=0.14468,
                             interpret=False), (q, q, q)


def _mla_decode(chip):
    """The latent decode kernel at the benchmark cell's shape: 256 rows of
    64 heads against pages of 128 rows of 640 lanes (512 + 64, padded)."""
    from apex_tpu.ops.mla_attention import mla_decode_attention
    return (functools.partial(mla_decode_attention, value_dim=512,
                              scale=0.14468, interpret=False),
            (_sds(chip, (256, 64, 640), BF16),
             _sds(chip, (1, 5121, 128, 640), BF16),
             _sds(chip, (256, 20), I32), _sds(chip, (256,), I32)))


def _grouped_matmul(block_m, rows):
    """An expert layer's two grouped matmuls over 16 held experts at the
    published widths: decode's layout (tiles of 32 rows) and a prompt's
    (128)."""
    def build(chip):
        from apex_tpu.ops import grouped_matmul as gmm
        tiles = gmm.num_tiles(16, block_m, rows)

        def fn(x, gate_up, down, tile_group, used):
            h = gmm.grouped_matmul(x, gate_up, tile_group, used,
                                   block_m=block_m, interpret=False)
            return gmm.grouped_matmul(h[:, :2048], down, tile_group, used,
                                      block_m=block_m, interpret=False)
        return fn, (_sds(chip, (tiles * block_m, 7168), BF16),
                    _sds(chip, (16, 7168, 4096), BF16),
                    _sds(chip, (16, 2048, 7168), BF16),
                    _sds(chip, (tiles,), I32), _sds(chip, (), I32))
    return build


def _flash_banded(window):
    """Cell 8's attention: 32 query heads over 4 key/value heads of 128 at
    8,192 tokens, forward and the two-kernel backward over the banded
    grids, under a window of 1,024 or without one."""
    def build(chip):
        q = _sds(chip, (2, 32, 8192, 128), BF16)
        k = _sds(chip, (2, 4, 8192, 128), BF16)
        fn = _sum_grad(functools.partial(
            flash_attention, causal=True, window=window, scale=128 ** -0.5,
            interpret=False), (0, 1, 2))
        return fn, (q, k, k)
    return build


def _grouped_matmul_train(chip):
    """Cell 8's expert layer: 16 held experts of 2,304 x 2 x 896 over the
    worst-case rows of 16,384 tokens x top 8, tiles of 256: forward, dx and
    dw of both grouped matmuls."""
    from apex_tpu.ops import grouped_matmul as gmm
    from apex_tpu.transformer import moe_dropless
    bm = moe_dropless._block_m(16384 * 8)
    tiles = gmm.num_tiles(16, bm, 16384 * 8)

    def fn(x, gate_up, down, tile_group, used):
        h = gmm.grouped_matmul(x, gate_up, tile_group, used, block_m=bm,
                               interpret=False)
        return gmm.grouped_matmul(h[:, :896], down, tile_group, used,
                                  block_m=bm, interpret=False)
    return _sum_grad(fn, (0, 1, 2)), (
        _sds(chip, (tiles * bm, 2304), BF16),
        _sds(chip, (16, 2304, 1792), BF16), _sds(chip, (16, 896, 2304), BF16),
        _sds(chip, (tiles,), I32), _sds(chip, (), I32))


def _moe_rows_sorted_train(chip):
    """Cell 8's dispatch and, with the scale and the row dot, its combine's
    cotangent: 16,384 tokens of 2,304 into the 135,168 padded rows."""
    from apex_tpu.ops import moe_rows
    rows = 528 * 256

    def fn(x, src, used, scale, ys):
        xs = moe_rows.sorted_rows(x, src, used, block_m=256, interpret=False)
        d_ys, dot = moe_rows.sorted_rows(x, src, used, block_m=256,
                                         scale=scale, dot_with=ys,
                                         interpret=False)
        return xs, d_ys, dot
    return fn, (_sds(chip, (16384, 2304), BF16), _sds(chip, (rows,), I32),
                _sds(chip, (), I32), _sds(chip, (rows,), F32),
                _sds(chip, (rows, 2304), BF16))


def _moe_rows_tokens_train(chip):
    """Cell 8's combine (and its dispatch's cotangent): the live rows of
    135,168 summed into 16,384 tokens x top 8."""
    from apex_tpu.ops import moe_rows

    def fn(ys, idx, wm, used):
        return moe_rows.token_rows(ys, idx, wm, used, block_m=256,
                                   interpret=False)
    return fn, (_sds(chip, (528 * 256, 2304), BF16),
                _sds(chip, (16384, 8), I32), _sds(chip, (16384, 8), F32),
                _sds(chip, (), I32))



def _flash_gqa_d256(chip):
    """Cell 9's full-attention layer: 16 query heads over 2 key/value heads
    of 256 at 16,384 tokens (the banded grids at 1,024-blocks under their
    64 MB VMEM scope), forward, dk/dv over a group of 8 and dq."""
    q = _sds(chip, (1, 16, 16384, 256), BF16)
    k = _sds(chip, (1, 2, 16384, 256), BF16)
    fn = _sum_grad(functools.partial(flash_attention, causal=True,
                                     scale=256 ** -0.5, interpret=False),
                   (0, 1, 2))
    return fn, (q, k, k)


def _gated_delta_scan(chip):
    """Cell 9's gated delta rule: 32 heads of 128 x 128 over 16,384 tokens
    in chunks of 128: what a chunk knows alone (4 chunks a program) and the
    walk (8 heads a program, the states kept), forward and backward."""
    from apex_tpu.ops import gated_delta
    q = _sds(chip, (1, 32, 16384, 128), BF16)
    g = _sds(chip, (1, 32, 16384), F32)
    fn = _sum_grad(lambda *a: gated_delta.gated_delta_rule(
        *a, interpret=False)[0], (0, 1, 2, 3, 4))
    return fn, (q, q, q, g, g)


CASES = {
    "flash_fwd_bwd_b8_s1024": _flash(1024, 8),
    "flash_fwd_bwd_b2_s4096": _flash(4096, 2),
    "lm_head_ce_fwd_bwd_n8192_v32768": _lm_head_ce,
    "paged_decode_bf16": _paged_decode(False),
    "paged_decode_fp8_kv": _paged_decode(True),
    "paged_decode_b64_h16_p385x128": _paged_decode_cell3,
    # one page of 32 heads of 1,024 tokens, twice, is 16 MB: a program
    # takes 16 heads and the grid is (b, 2)
    "paged_decode_two_head_blocks": _paged_decode_at(B, 32, 17, 1024, 2),
    "kv_write_bf16": _kv_write(False),
    "kv_write_fp8_kv": _kv_write(True),
    "layer_norm_fwd_bwd_8192x1024": _layer_norm,
    "fused_ce_fwd_bwd_8192x32768": _fused_ce,
    "fp8_dequant_matmul_h1024_linears": _fp8_matmul,
    "fused_shard_update_adam": _shard_update,
    "flash_fwd_latent_prefill_d192": _flash_fwd_d192,
    "mla_decode_b256_h64_w640": _mla_decode,
    "moe_grouped_matmul_decode_16x7168x4096": _grouped_matmul(32, 2048),
    "moe_grouped_matmul_prefill_16x7168x4096": _grouped_matmul(128, 8192),
    "flash_window1024_gqa_fwd_bwd_b2_s8192": _flash_banded(1024),
    "flash_gqa_fwd_bwd_b2_s8192": _flash_banded(None),
    "moe_grouped_matmul_train_16x2304x1792": _grouped_matmul_train,
    "moe_rows_sorted_train_16384x2304": _moe_rows_sorted_train,
    "moe_rows_tokens_train_16384x8x2304": _moe_rows_tokens_train,
    "flash_gqa_d256_fwd_bwd_s16384": _flash_gqa_d256,
    "gated_delta_scan_fwd_bwd_h32_s16384": _gated_delta_scan,
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, args = CASES[case](chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_decode_reads_the_pool_where_it_lies(chip):
    """The kernel takes the pool as it is (``pl.ANY``) and fetches pages by
    table entry itself, so beside it the program holds only the query's
    padding and the result's transpose: no gather the size of a row's
    table of pages (64 x 8 pages = 134 MB), no copy of the leaf (202 MB),
    and the instruction keeps the name the trace reader looks for."""
    fn, args = _paged_decode_cell3(chip)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(r"%apx_paged_decode_attention[\w.]* = ", text)
    assert _pool_traffic(text, args[1].size) == []
    assert "gather" not in text
    q_bytes = 64 * H * 2 * D * 2            # [q | 0] in bf16
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * q_bytes


#: the token write at each serve cell's pool leaf and decode batch: cell 3
#: (gpt2-medium), cells 5 and 6 (the latent leaves of GigaChat3 and
#: LongCat), cell 7 (MiniCPM-SALA's sparse layers)
TOKEN_WRITES = {
    "cell3_bf16_16x385x128x128_b64": ((16, 385, 128, 128), 64),
    "cell5_bf16_1x5121x128x640_b256": ((1, 5121, 128, 640), 256),
    "cell6_bf16_1x3073x128x640_b256": ((1, 3073, 128, 640), 256),
    "cell7_bf16_2x12289x64x256_b32": ((2, 12289, 64, 256), 32),
}


def _pallas_calls(fn, *args):
    """The parameters of every ``pallas_call`` in ``fn``'s jaxpr, nested
    jits included."""
    return [eqn.params for eqn in iter_eqns(jax.make_jaxpr(fn)(*args),
                                            skip_kernel_bodies=True)
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("cell", list(TOKEN_WRITES))
def test_token_write_moves_a_group_of_rows(chip, cell):
    """A decode step's write of one token a row takes ``G`` > 1 rows a
    program at every serve cell's shapes (the form is chosen at trace time
    from shapes, so this is what says that it engages): the grid is
    ``ceil(b / G)``, the program holds ``G`` tiles of 16 rows of every kv
    head and a DMA semaphore each inside the VMEM budget, and the chip's
    compiler takes it and updates the donated leaf where it lies."""
    shape, b = TOKEN_WRITES[cell]
    kv, _, _, width = shape
    fn = functools.partial(paged_kv_write_rows, interpret=False)
    args = (_sds(chip, shape, BF16), _sds(chip, (b,), I32),
            _sds(chip, (b,), I32), _sds(chip, (b, kv, width), BF16))
    call, = _pallas_calls(fn, *args)
    tiles, sems = (v.aval for v in call["jaxpr"].invars[-2:])
    group = tiles.shape[0]
    assert group > 1 and tuple(call["grid_mapping"].grid) == (-(-b // group),)
    assert tiles.shape == (group, kv, 16, width) and sems.shape == (group,)
    assert tiles.size * 2 <= _DECODE_BUFFER_BYTES
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    assert re.search(r"%apx_kv_write[.\d]* = \S+ custom-call\(",
                     compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == args[0].size * 2
    assert mem.temp_size_in_bytes < 2 ** 20


#: what a device trace of the chip is joined on (benchmarks/harness/
#: span_reduce.py): a Pallas kernel's instruction is named by the innermost
#: scope around its call, one name per direction; XLA's own instructions
#: keep the scope path in their ``op_name``
NAMED = {
    "flash_fwd_bwd_b8_s1024": (
        _flash(1024, 8), (r"%apx_flash_attention_fwd[.\d]* = ",
                          r"%apx_flash_attention_bwd[.\d]* = ")),
    "lm_head_ce_fwd_bwd_n8192_v32768": (
        _lm_head_ce, (r"%apx_lm_head_ce_fwd[.\d]* = ",
                      r"%apx_lm_head_ce_bwd[.\d]* = ")),
    "kv_write_token_and_prompt": (
        _kv_write(False), (r'op_name="[^"]*apx:kv_write/',
                           r"%apx_kv_write[.\d]* = ")),
    "mla_decode": (_mla_decode, (r"%apx_mla_decode_attention[.\d]* = ",)),
    "moe_grouped_matmul": (_grouped_matmul(32, 2048),
                           (r"%apx_moe_grouped_matmul[.\d]* = ",)),
    "flash_window": (_flash_banded(1024),
                     (r"%apx_flash_attention_window_fwd[.\d]* = ",
                      r"%apx_flash_attention_window_bwd[.\d]* = ")),
    # a bare ``jax.grad`` wraps the scope (``transpose_jvp_apx_..._``); under
    # a rematerialised block, as cell 8's step, the names are the scopes'
    # own (``test_mellum_train_step_compiles_for_v5e_under_15_gb`` pins them)
    "moe_grouped_matmul_train": (
        _grouped_matmul_train, (r"%\w*apx_moe_grouped_matmul_*[.\d]* = ",
                                r"%\w*apx_moe_grouped_matmul_dw_*[.\d]* = ")),
    # names of their own: ``moe_grouped_matmul_train_roofline`` reads
    # ``^apx_moe_grouped_matmul`` and has to keep reading only the matmuls
    "moe_rows_sorted": (_moe_rows_sorted_train,
                        (r"%apx_moe_rows_sorted[.\d]* = ",)),
    "moe_rows_tokens": (_moe_rows_tokens_train,
                        (r"%apx_moe_rows_tokens[.\d]* = ",
                         r"%apx_moe_rows_open[.\d]* = ")),
}


@pytest.mark.parametrize("case", list(NAMED))
def test_compiled_text_names_direction_and_scope(chip, case):
    build, patterns = NAMED[case]
    fn, args = build(chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    for pattern in patterns:
        assert re.search(pattern, text), pattern


@pytest.fixture
def no_interpret(monkeypatch):
    """The kernels' backend rule, steered for a described chip."""
    def rule(interpret):
        return False if interpret is None else interpret
    monkeypatch.setattr(_compat, "resolve_interpret", rule)


def test_layers_call_one_lowered_flash_kernel_a_direction(chip, no_interpret):
    """A four-layer GPT's forward + backward for the described chip: the
    kernel calls are jitted on their own (``_flash_fwd_impl``,
    ``_flash_bwd_impl``), so the program holds ONE lowered function a
    direction, one Mosaic module in it, that the four layers call; the chip's
    compiler still names four instructions a direction by the scopes inside
    (what the benchmark's rooflines find). Without the jit each layer's call
    site is traced and lowered again at every lowering, cache hit or not:
    PR 44 lost 21 s of ``gpt2l-train-4chip``'s warm set-up to that."""
    from apex_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=512, max_seq_len=256, hidden_size=128,
                          num_layers=4, num_heads=2, fused_lm_head=False))
    ids = _sds(chip, (2, 256), I32)
    params = _place(chip, jax.eval_shape(
        functools.partial(model.init, jax.random.PRNGKey(0)), ids))
    lowered = jax.jit(jax.grad(
        lambda p, ids: model.loss(p, ids, ids))).lower(params, ids)
    text = lowered.as_text()
    for fn in ("_flash_fwd_impl", "_flash_bwd_impl"):
        assert text.count(f"func.func private @{fn}(") == 1
        assert text.count(f"call @{fn}(") == 4
    assert text.count("tpu_custom_call") == 2
    hlo = lowered.compile().as_text()
    for direction in ("fwd", "bwd"):
        assert _named(hlo, f"apx_flash_attention_{direction}") == 4


def _mellum_step(chip, layer_types=None):
    """Cell 8's step (``benchmarks/configs/mellum2-12b-ep4-l4.json`` at its
    published widths, 2 x 8,192 tokens, ``amp`` O2 + FusedAdam through
    ``make_train_step(has_aux=True)``, per-block recomputation), compiled
    for the described chip on shapes only: ``(state's shapes, tokens,
    compiled)``. ``layer_types``: other layers than the file's four."""
    import json
    import os
    from apex_tpu import amp
    from apex_tpu.models import mellum as ml
    from apex_tpu.optimizers import FusedAdam
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "mellum2-12b-ep4-l4.json")) as f:
        c = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train-s8192-ep4share.json")) as f:
        t = json.load(f)
    yarn = c["rope_parameters"]["full_attention"]
    cfg = ml.MellumConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        layer_types=tuple(layer_types or c["layer_types"]),
        sliding_window=c["sliding_window"],
        first_expert=c["held"]["first_expert"],
        n_local_experts=c["held"]["local_experts"],
        rope_theta=float(yarn["rope_theta"]), rope_scaling=tuple(sorted(
            (k, v) for k, v in yarn.items()
            if k not in ("rope_type", "rope_theta", "attention_factor"))))
    amp_model, opt = amp.initialize(
        lambda p, i: ml.forward(cfg, p, i)[0], FusedAdam(lr=3e-4),
        opt_level="O2", verbosity=0)

    def init_state(key):
        params = amp_model.cast_params(ml.init_params(cfg, key))
        return params, opt.init(params), \
            opt._amp_stash.loss_scalers[0].state

    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    step = amp.make_train_step(
        lambda p, i, l: ml.loss(cfg, p, i, l), opt, has_aux=True)
    ids = _sds(chip, (t["batch"], t["seq"]), I32)
    return state, ids, step._jitted.lower(False, *_place(chip, state), ids,
                                          ids).compile()


def _held_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _named(hlo, name):
    """How many instructions of a compiled program carry ``name``."""
    return len(re.findall(rf"%{name}[.\d]* = ", hlo))


def test_mellum_train_step_compiles_for_v5e_under_15_gb(chip, no_interpret):
    """Cell 8's step lowers for the described chip on shapes only and
    fits: the state is 14 B a parameter (595.2M: 8.33 GB), the step under
    15.0 GB; the window layers' kernels and the full layer's carry their
    own names, and a layer's forward flash kernel is there ONCE (its block
    keeps the kernel's operands and results: ``models/mellum.py:hidden``)."""
    state, _, compiled = _mellum_step(chip)
    n_params = sum(x.size for x in jax.tree.leaves(state[0]))
    assert n_params == 595_153_152
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(state)) < 14.01 * n_params
    # 14.382 GB with the flash kernel's operands and its two results of the
    # four layers kept (1.22 GB of them, of which 0.68 show at the step's
    # peak; 13.776 with the results alone, 13.703 when the backward rebuilt
    # all five); 14.217 before the expert layer's rows moved through
    # ``ops/moe_rows``
    assert _held_bytes(compiled) < 14.6e9
    hlo = compiled.as_text()
    for name, n in (("apx_flash_attention_window_fwd", 3),
                    ("apx_flash_attention_window_bwd", 6),
                    ("apx_flash_attention_fwd", 1),
                    ("apx_flash_attention_bwd", 2),
                    ("apx_moe_grouped_matmul_dw", 8),
                    ("apx_moe_grouped_matmul", 24),
                    # dispatch forward and recomputed, the combine's
                    # cotangent; combine forward (its recomputation is dead
                    # code), the dispatch's cotangent; each of the twenty
                    # behind the pass that opens its source's rows
                    ("apx_moe_rows_sorted", 12),
                    ("apx_moe_rows_tokens", 8),
                    ("apx_moe_rows_open", 20)):
        assert _named(hlo, name) == n, name
    # no gather over the worst-case row buffers is left
    assert not re.search(r"fusion[.\d]* = bf16\[13(1072|5168),2304\]", hlo)


def _qwen3_next_step(chip, **sizes):
    """Cell 9's step (``benchmarks/configs/qwen3-next-80b-ep32-l4.json`` at
    its published widths, 1 x 16,384 tokens, ``amp`` O2 + FusedAdam with the
    family's ``keep_fp32`` through ``make_train_step(has_aux=True)``,
    per-block recomputation), compiled for the described chip on shapes
    only: ``(state's shapes, compiled)``. ``sizes``: other fields of the
    model's description than the file's."""
    from apex_tpu import amp
    from apex_tpu.models import qwen3_next as qn
    from apex_tpu.optimizers import FusedAdam
    from benchmarks.families import qwen3_next as family
    from benchmarks.harness import manifest
    cfg = dataclasses.replace(family.model_config(manifest.load_config(
        manifest.load_manifest(), "qwen3-next-80b-ep32-l4")), **sizes)
    amp_model, opt = amp.initialize(
        lambda p, i: qn.forward(cfg, p, i)[0], FusedAdam(lr=1e-6),
        opt_level="O2", verbosity=0, keep_fp32_predicate=qn.keep_fp32)

    def init_state(key):
        params = amp_model.cast_params(qn.init_params(cfg, key))
        return params, opt.init(params), \
            opt._amp_stash.loss_scalers[0].state

    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    step = amp.make_train_step(
        lambda p, i, l: qn.loss(cfg, p, i, l), opt, has_aux=True)
    ids = _sds(chip, (1, 16384), I32)
    return state, step._jitted.lower(False, *_place(chip, state), ids,
                                     ids).compile()


def test_qwen3_next_block_kinds_compile_for_v5e(chip, no_interpret):
    """Cell 9's step at its widths and two of its layers (one gated-delta,
    one full): the scan's two forward kernels are there twice (the block
    rebuilds the chunk states in its backward), its backward kernels once,
    the full layer's forward flash kernel once (kept by name); the decay
    leaves stay float32."""
    state, compiled = _qwen3_next_step(chip, num_layers=2,
                                       full_attention_interval=2)
    gdn = state[0]["layer_0"]["gdn"]
    assert gdn["A_log"].dtype == gdn["dt_bias"].dtype == F32
    assert gdn["qkvz"].dtype == BF16
    assert "attn" in state[0]["layer_1"]
    hlo = compiled.as_text()
    for name, n in (("apx_gdn_chunk_fwd", 2), ("apx_gdn_scan_fwd", 2),
                    ("apx_gdn_chunk_bwd", 1), ("apx_gdn_scan_bwd", 1),
                    ("apx_flash_attention_fwd", 1),
                    ("apx_flash_attention_bwd", 2),
                    ("apx_moe_grouped_matmul_dw", 4),
                    ("apx_moe_grouped_matmul", 12)):
        assert _named(hlo, name) == n, name


@pytest.mark.slow
def test_qwen3_next_train_step_compiles_for_v5e_under_15_gb(chip,
                                                            no_interpret):
    """The whole of cell 9's step fits: the state is 14 B a parameter
    (424.3M: 5.94 GB), the step 13.57 GB (two minutes of compiling: by
    hand, ``-m slow``)."""
    state, compiled = _qwen3_next_step(chip)
    n_params = sum(x.size for x in jax.tree.leaves(state[0]))
    assert n_params == 424_340_544
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(state)) < 14.01 * n_params
    assert _held_bytes(compiled) < 14.0e9
    assert _named(compiled.as_text(), "apx_gdn_chunk_fwd") == 6


def _flash_forwards(hlo):
    return (_named(hlo, "apx_flash_attention_window_fwd"),
            _named(hlo, "apx_flash_attention_fwd"))


def test_mellum_block_keeps_the_flash_kernels_operands_and_results(
        chip, no_interpret, monkeypatch):
    """Cell 8's step at its widths and two of its layers (one sliding, one
    full): the block's names policy is honoured down to the chip's
    program. One forward flash custom call a layer (two under a bare
    ``jax.checkpoint``: forward and recomputed), each backward kernel as
    before, and the step holds at most 1.1 x the kept ``q``, ``k``, ``v``,
    ``out`` and ``lse`` more (read: 9.940 against 9.480 GB, +0.460 of the
    0.608 kept; 9.644 with ``out`` and ``lse`` alone: the step's peak lies
    in the expert layer's backward, where some of a layer's residuals are
    already dead)."""
    kinds = ("sliding_attention", "full_attention")
    _, ids, kept = _mellum_step(chip, kinds)
    with monkeypatch.context() as m:
        real = jax.checkpoint
        m.setattr(jax, "checkpoint", lambda fn, **kw: real(fn))
        _, _, bare = _mellum_step(chip, kinds)
    kept_hlo, bare_hlo = kept.as_text(), bare.as_text()
    assert _flash_forwards(kept_hlo) == (1, 1)
    assert _flash_forwards(bare_hlo) == (2, 2)
    for name in ("apx_flash_attention_window_bwd", "apx_flash_attention_bwd"):
        assert _named(kept_hlo, name) == _named(bare_hlo, name) == 2, \
            name                                     # dk/dv and dq
    b, s = ids.shape
    # bf16 rows of 128: q and out of 32 heads, k and v of 4; a float32 lse
    a_layer = b * s * ((2 * 32 + 2 * 4) * 128 * 2 + 32 * 4)
    grown = _held_bytes(kept) - _held_bytes(bare)
    assert 0 < grown <= 1.1 * a_layer * len(kinds), grown


#: cell 1's step (gpt2-medium's widths, 8 x 1,024 tokens, two layers)
#: compiled for the described chip from the parent of the PR that named the
#: flash kernel's results (``git archive`` of ac09a86, the same engine):
#: instructions, kinds of (operation, result shape), and a digest of the
#: census
CELL1_CENSUS = (4146, 288, "c46f4539e8dc8fc4")


def test_flash_result_names_leave_cell_1s_step_as_it_was(chip, no_interpret,
                                                         chip_smoke):
    """``checkpoint_name`` is an identity that lowers to nothing: a model
    whose blocks are not recomputed (cells 1, 2, 4) compiles to the
    parent's program, instruction for instruction by operation and shape."""
    import collections
    import hashlib
    sz = dataclasses.replace(chip_smoke.FULL, vocab=50304, layers=2)
    _, step, init_state = chip_smoke.build_train(sz)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0),
                           jax.ShapeDtypeStruct((1, sz.seq), I32))
    ids = _sds(chip, (sz.batch, sz.seq), I32)
    text = step._jitted.lower(False, *_place(chip, state), ids,
                              ids).compile().as_text()
    census = collections.Counter(
        (op, dims) for _, dims, op, _ in _INSTRUCTION.findall(text))
    digest = hashlib.sha1(repr(sorted(census.items())).encode()).hexdigest()
    assert (sum(census.values()), len(census), digest[:16]) == CELL1_CENSUS
    assert _named(text, "apx_flash_attention_fwd") == 2
    assert _named(text, "apx_flash_attention_bwd") == 2


# ---------------------------------------------------------------------------
# whole programs (slow): the train step and the serve programs of chip_smoke
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_step_compiles_for_v5e(chip, no_interpret, chip_smoke):
    """``amp.make_train_step`` (O2 + FusedAdam) on the full GPT — the step
    chip_smoke builds, on shapes only (nothing full-width is materialised
    on the host); must fit one chip's 16 GB."""
    smoke = chip_smoke
    sz = smoke.FULL
    _, step, init_state = smoke.build_train(sz)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0),
                           jax.ShapeDtypeStruct((1, sz.seq), I32))
    ids = _sds(chip, (sz.batch, sz.seq), I32)
    compiled = step._jitted.lower(False, *_place(chip, state), ids,
                                  ids).compile()
    smoke._require_kernels(smoke._kernel_calls(compiled.as_text()),
                           flash_attention=2, lm_head_ce=2)
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15e9


#: cell 4 of the benchmark (gpt2-large on four chips, tp=4 + SP, global
#: batch 8 x 1,024) at two of its 36 layers
CELL4 = dict(vocab=50304, hidden=1280, heads=20, seq=1024, batch=8,
             mc_layers=2)


def _compile_example_step(topo, smoke):
    """``examples/gpt/main_gpt.py:make_step_fns`` on the four described
    chips at ``CELL4``: the mesh, the state's shapes and the compiled
    step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from apex_tpu.models import GPT
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps
    sz = dataclasses.replace(smoke.FULL, **CELL4)
    ps.destroy_model_parallel()
    try:
        mesh = ps.initialize_model_parallel(
            tensor_model_parallel_size_=4, devices=list(topo.devices))
        model = GPT(smoke._gpt_config(sz, layers=sz.mc_layers,
                                      sequence_parallel=True))
        init_f, step_f = smoke._main_gpt().make_step_fns(
            mesh, model, FusedAdam(lr=3e-4, master_weights=True))
        ids = jax.ShapeDtypeStruct(
            (sz.batch, sz.seq), I32,
            sharding=NamedSharding(mesh, P(ps.DATA_AXIS)))
        replicated = NamedSharding(mesh, P())
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=replicated),
            jax.eval_shape(init_f, ids))
        compiled = step_f.lower(*state, ids, ids).compile()
    finally:
        ps.destroy_model_parallel()
    return mesh, state, compiled


def test_example_train_step_updates_its_state_in_place(topo, no_interpret,
                                                       chip_smoke):
    """``examples/gpt/main_gpt.py:make_step_fns`` on the four described
    chips, as cell 4 and ``chip_smoke.py --chips 4`` run it: the step
    donates its variables, optimizer state and scaler state, and the
    chip's compiler aliases every leaf of them onto the output. Before
    PR 33 nothing was donated: the state was resident twice and each step
    allocated an output buffer for every leaf on every chip while the
    chips waited (~1,750 leaves a chip, ~170 ms a step, at full depth)."""
    smoke = chip_smoke
    _, state, compiled = _compile_example_step(topo, smoke)
    text = compiled.as_text()
    smoke._require_kernels(smoke._kernel_calls(text), flash_attention=2)
    leaves = jax.tree.leaves(state)
    # the module's header line lists every aliased pair: the compiler pads
    # small leaves, so the byte count alone would let one scalar go missing
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         text.split("\n", 1)[0])
    assert sorted(map(int, aliased)) == list(range(len(leaves)))
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in leaves)


#: what the step of the commit before the layers took the ring (2d6d991)
#: compiles to at ``CELL4`` on the described v5e:2x2, arguments + outputs +
#: temporaries - aliased (deviceless compile, PR 41)
CELL4_PARENT_BYTES = 986_164_736


def test_example_train_step_rings_its_reduce_scatters(topo, no_interpret,
                                                      chip_smoke):
    """The same step: tensor ranks follow the 2x2's links (0, 1, 3, 2:
    the enumeration order's 1 -> 2 and 3 -> 0 are diagonal), every
    reduce-scatter of a block's activation travels as asynchronous
    ``collective-permute-start/done`` pairs beside the pieces of its own
    matmul (row forward and column backward of two layers x two linears,
    3 hops x 2 directions each) and none is left blocking; the gathers
    are the device's (a gather ring lost on the chip, ``overlap._gathered``).
    The step holds no more memory than the parent's."""
    mesh, _, compiled = _compile_example_step(topo, chip_smoke)
    assert [d.id for d in mesh.devices.flat] == [0, 1, 3, 2]
    text = compiled.as_text()
    layers, linears, hops, ways = CELL4["mc_layers"], 2, 3, 2
    assert text.count(" collective-permute-start(") \
        == 2 * layers * linears * hops * ways
    assert text.count(" collective-permute-done(") \
        == text.count(" collective-permute-start(")
    blocking = [line for line in text.splitlines()
                if re.search(r" reduce-scatter\(|%reduce_scatter[\w.]* = ",
                             line) and "/block_" in line]
    assert blocking == []
    assert re.search(r" all-gather\(.*/block_\d+/", text)
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= 1.05 * CELL4_PARENT_BYTES


def _compile_serve(chip, smoke, sz, fp8_kv=False):
    """The ``ServeEngine``'s own decode and prefill programs, compiled for
    the described chip with the kernel paths the engine picks on a TPU
    (named here: its defaults ask ``_compat.on_tpu``, which no fixture of
    this file steers, so on this host they are the XLA reference), pool
    donated. Returns the engine and the two executables."""
    from apex_tpu import serve
    from apex_tpu.models import GPT
    cfg = smoke._gpt_config(sz)
    params = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), I32))["params"])
    eng = serve.ServeEngine(
        cfg, jax.tree.map(lambda x: _sds(chip, x.shape, BF16), params),
        num_pages=sz.num_pages, max_seq_len=sz.max_seq_len,
        max_prompt_len=sz.max_prompt_len, max_batch=sz.max_batch,
        fp8_kv=fp8_kv, paged_impl="kernel", attention_impl="flash",
        interpret=False)
    eng.state = _place(chip, eng.state)
    decode, prefill = smoke._serve_programs(eng, sharding=chip)
    return eng, decode.compile(), prefill.compile()


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)$",
    re.M)


def _pool_traffic(text, leaf_elems):
    """Instructions of a compiled program that XLA added to move the pool:
    rematerialisation clones (``fusion.7.remat_compressed``), and every
    copy, slice, update or fusion whose result has as many elements as one
    layer's pool leaf. The in-place program has none: its only
    instructions of that size are the aliased Pallas writes."""
    found = []
    for name, dims, op, _ in _INSTRUCTION.findall(text):
        elems = 1
        for n in filter(None, dims.split(",")):
            elems *= int(n)
        if ".remat" in name or (elems == leaf_elems and op in (
                "copy", "slice", "dynamic-slice", "dynamic-update-slice",
                "scatter", "fusion")):
            found.append(f"{name} = [{dims}] {op}")
    return found


#: cell 3 of the benchmark (gpt2-medium, 64 clients): the widths, pool and
#: batch at which XLA cloned and copied the pool before PR 24
CELL3 = dict(vocab=50304, hidden=1024, heads=16, max_seq_len=1024,
             max_prompt_len=512, max_batch=64, num_pages=385)


def test_serve_programs_update_the_pool_in_place(chip, chip_smoke):
    """Two layers of cell 3 (the census does not depend on depth: at the
    parent the same two layers hold 8 copies and 4 slices of a layer's
    pool in decode, 4 copies in prefill)."""
    sz = dataclasses.replace(chip_smoke.FULL, layers=2, **CELL3)
    eng, decode, prefill = _compile_serve(chip, chip_smoke, sz)
    leaf = eng.state.pools[0].size
    for program in (decode, prefill):
        assert _pool_traffic(program.as_text(), leaf) == []
        assert program.memory_analysis().alias_size_in_bytes \
            >= eng.ccfg.pool_bytes()


#: the latent-attention cell of the benchmark (GigaChat3.1-702B-A36B, one
#: chip's share of EP16) at two of its five layers: one dense, one expert
LATENT = dict(vocab_size=16032, hidden_size=7168, num_layers=2, num_heads=64,
              q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
              qk_rope_head_dim=64, v_head_dim=192, intermediate_size=18432,
              moe_intermediate_size=2048, n_routed_experts=256,
              num_experts_per_tok=8, n_group=8, topk_group=4,
              first_k_dense_replace=1, routed_scaling_factor=2.5,
              n_local_experts=16, rope_theta=1e5, max_seq_len=2560,
              rope_scaling=(("beta_fast", 32), ("beta_slow", 1),
                            ("factor", 64), ("mscale", 1),
                            ("mscale_all_dim", 1),
                            ("original_max_position_embeddings", 4096)))


#: cell 6 of the benchmark (LongCat-Flash-Chat, one chip's share of EP32) at
#: one of its four layers: two latent attentions (two leaves), two dense
#: feed-forwards, the expert layer on its shortcut
LONGCAT = dict(vocab_size=16384, hidden_size=6144, num_layers=1, num_heads=64,
               q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, ffn_hidden_size=12288,
               expert_ffn_hidden_size=2048, n_routed_experts=512,
               zero_expert_num=256, moe_topk=12, routed_scaling_factor=6.0,
               n_local_experts=16, max_seq_len=1536)


def _latent_model(name):
    """``(served model, its init_params, the cell's engine sizes)``."""
    if name == "deepseek":
        from apex_tpu.models import deepseek as ds
        from apex_tpu.serve.deepseek import DeepseekServed
        cfg = ds.DeepseekConfig(**LATENT)
        return DeepseekServed(cfg), ds.init_params, dict(
            num_pages=5121, max_seq_len=2560, max_prompt_len=1024)
    from apex_tpu.models import longcat as lc
    from apex_tpu.serve.longcat import LongcatServed
    cfg = lc.LongcatConfig(**LONGCAT)
    return LongcatServed(cfg), lc.init_params, dict(
        num_pages=3073, max_seq_len=1536, max_prompt_len=512)


@pytest.mark.parametrize("name", ["deepseek", "longcat"])
def test_latent_serve_programs_update_the_pool_in_place(chip, chip_smoke,
                                                        name):
    """The same engine over the latent-attention models at their cells'
    shapes: decode (absorbed attention, grouped expert matmuls) and prefill
    (flash at d = 192; LongCat's 128-lane V padded to it) compile with their
    kernels, update every latent leaf where it lies (LongCat: two a layer)
    and need next to nothing beside their arguments."""
    from apex_tpu import serve
    model, init_params, sizes = _latent_model(name)
    params = jax.eval_shape(
        lambda: init_params(model.cfg, jax.random.PRNGKey(0)))
    eng = serve.ServeEngine(
        model, _place(chip, params), page_size=128, max_batch=256,
        paged_impl="kernel", attention_impl="flash", interpret=False,
        **sizes)
    eng.state = _place(chip, eng.state)
    assert [p.shape for p in eng.state.pools] == \
        [(1, sizes["num_pages"], 128, 640)] * (
            model.cfg.num_layers * model.leaves_per_layer)
    decode, prefill = (p.compile() for p in chip_smoke._serve_programs(
        eng, sharding=chip))
    leaf = eng.state.pools[0].size
    for program, kernels in (
            (decode, ("apx_mla_decode_attention", "apx_moe_grouped_matmul",
                      "apx_kv_write")),
            (prefill, ("apx_flash_attention_fwd", "apx_moe_grouped_matmul",
                       "apx_kv_write"))):
        text = program.as_text()
        for kernel in kernels:
            assert re.search(rf"%{kernel}[.\d]* = ", text), kernel
        assert _pool_traffic(text, leaf) == []
        mem = program.memory_analysis()
        assert mem.alias_size_in_bytes >= eng.ccfg.pool_bytes()
        assert mem.temp_size_in_bytes < 0.5e9


#: cell 7 of the benchmark (MiniCPM-SALA, published layers 9-20) at three of
#: its twelve layers: one sparse, two lightning
SALA = dict(vocab_size=73448, hidden_size=4096, intermediate_size=16384,
            max_seq_len=24576)


def _moved_whole(text, shape):
    """Instructions whose result is a whole cache leaf of ``shape`` and that
    are not the kernel updating it where it lies."""
    dims = ",".join(map(str, shape))
    return [name for name, d, op, _ in _INSTRUCTION.findall(text)
            if d == dims and op not in ("parameter", "get-tuple-element",
                                        "bitcast", "custom-call", "tuple")]


def test_state_and_selection_leaves_are_updated_in_place(chip, chip_smoke):
    """The decode program and the CHUNK program of an engine with
    ``prefill_chunk`` over both kinds of cache: the K|V pool leaf (805 MB),
    the recurrent-state leaves (67 MB each) and the compressed-key leaf are
    arguments aliased to results; nothing copies a pool-sized or a
    state-sized buffer, and the programs need next to nothing beside their
    arguments. Their kernels are there by name."""
    from apex_tpu import serve
    from apex_tpu.models import minicpm_sala as ms
    from apex_tpu.serve.minicpm_sala import MiniCPMSalaServed
    cfg = ms.MiniCPMSalaConfig(
        mixer_types=(ms.SPARSE, ms.LIGHTNING, ms.LIGHTNING), **SALA)
    params = jax.eval_shape(
        lambda: ms.init_params(cfg, jax.random.PRNGKey(0)))
    eng = serve.ServeEngine(
        MiniCPMSalaServed(cfg), _place(chip, params), num_pages=32 * 384 + 1,
        max_seq_len=24576, max_prompt_len=16384, page_size=64, max_batch=32,
        prefill_chunk=2048, paged_impl="kernel", attention_impl="flash",
        interpret=False)
    eng.state = _place(chip, eng.state)
    assert [s.shape for s in eng.state.states] == [(32, 32, 128, 128)] * 2
    assert [k.shape for k in eng.state.ckeys] == [(12289 * 4, 256)]
    decode, chunk = (p.compile() for p in chip_smoke._serve_programs(
        eng, sharding=chip))
    for program, kernels, room in (
            (decode, ("apx_lightning_decode", "apx_sparse_decode_attention",
                      "apx_kv_write"), 0.1e9),
            (chunk, ("apx_lightning_prefill", "apx_flash_attention_fwd",
                     "apx_kv_write"), 1.0e9)):       # 2,048 rows x 16,384
        text = program.as_text()
        for kernel in kernels:
            assert re.search(rf"%{kernel}[.\d]* = ", text), kernel
        assert _pool_traffic(text, eng.state.pools[0].size) == []
        assert _moved_whole(text, eng.state.pools[0].shape) == []
        assert _moved_whole(text, eng.state.states[0].shape) == []
        mem = program.memory_analysis()
        assert mem.alias_size_in_bytes >= eng.ccfg.pool_bytes()
        assert mem.temp_size_in_bytes < room


@pytest.mark.slow
@pytest.mark.parametrize("shapes,fp8_kv", [("smoke", False), ("smoke", True),
                                           ("cell3", False)])
def test_serve_programs_compile_for_v5e(chip, chip_smoke, shapes, fp8_kv):
    """Decode and prefill at full depth, at the shapes chip_smoke serves
    and at cell 3's: the kernels are there, the pool is updated in place,
    and the programs need next to nothing beside their arguments (cell 3's
    decode took 7.39 GB of temporaries for its pool copies before PR 24)."""
    smoke = chip_smoke
    sz = smoke.FULL if shapes == "smoke" else dataclasses.replace(
        smoke.FULL, layers=24, **CELL3)
    eng, decode, prefill = _compile_serve(chip, smoke, sz, fp8_kv)
    smoke._require_kernels(smoke._kernel_calls(decode.as_text()),
                           paged_attn=1)
    smoke._require_kernels(smoke._kernel_calls(prefill.as_text()),
                           flash_attention=1)
    leaf = eng.state.pools[0].size
    for program in (decode, prefill):
        assert _pool_traffic(program.as_text(), leaf) == []
        assert program.memory_analysis().temp_size_in_bytes < 0.5e9
