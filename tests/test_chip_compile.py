"""Ask the TPU v5e compiler, without a chip, whether it accepts every
Pallas kernel on the two paths `chip_smoke.py` drives — at the smoke's
real widths (on-chip-measurement guide §2, rehearsal 3).

Interpret mode cannot see what Mosaic refuses (block shapes that break
the (8, 128) rule, unsupported layout changes, VMEM overflow); these
compiles can, and cost no chip time.  Nothing here runs: a pass means
"compiles for v5e", never "measured".

The same is asked for the four chips of one host: a Mosaic kernel cannot
be partitioned automatically, so in a program traced over a mesh
(`ops/mosaic.py`) every kernel has to arrive at the compiler inside a
`shard_map`.

The topology is described inside a module-scoped fixture so that only
the xdist worker that owns this file loads the TPU library; everything
built from it (shardings, meshes, shapes) is built in the test.
"""
import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from incubator_mxnet_tpu.ops import mosaic

# ops/__init__ re-exports functions under the module names
_flash = importlib.import_module("incubator_mxnet_tpu.ops.flash_attention")
_paged = importlib.import_module("incubator_mxnet_tpu.ops.paged_attention")
_xent = importlib.import_module("incubator_mxnet_tpu.ops.xent_kernel")
_dropout = importlib.import_module("incubator_mxnet_tpu.ops.dropout_kernel")
_scan = importlib.import_module("incubator_mxnet_tpu.ops.selective_scan")
_moe = importlib.import_module("incubator_mxnet_tpu.ops.moe_experts")
_sparse = importlib.import_module("incubator_mxnet_tpu.ops.sparse_attention")

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e:2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises when it is absent
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache
    # but cannot be read back without a chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def one_chip(v5e):
    return SingleDeviceSharding(v5e[0])


# serve phase widths (H=16, D=64, block 16, 512 positions), batch 32
_B, _H, _D, _BS, _NBPS = 32, 16, 64, 16, 32
_NB = _B * _NBPS + 1
_PAGE = (_NB, _BS, _H * _D)     # a position a row, a head a run of D
_SCALE = (_NB, _BS, _H)


# the served cell's table and pool (gpt2-medium.serve-batch): 64 blocks a
# sequence, 1025 in the pool
_CELL_NBPS, _CELL_NB = 64, 1025


def _paged_bf16(nbps=_NBPS, nb=_NB):
    page = (nb,) + _PAGE[1:]
    return lambda S: _paged._paged_core.lower(
        S((_B, _H, _D), bf16), S(page, bf16), S(page, bf16),
        S((_B, nbps), i32), S((_B,), i32), interpret=False)


def _paged_int8(nbps=_NBPS, nb=_NB):
    page, scale = (nb,) + _PAGE[1:], (nb,) + _SCALE[1:]
    return lambda S: _paged._paged_core_q8.lower(
        S((_B, _H, _D), bf16), S(page, i8), S(page, i8),
        S(scale, f32), S(scale, f32),
        S((_B, nbps), i32), S((_B,), i32), interpret=False)


# the hybrid cell's widths (jamba2-3b.serve-docs): 20 query heads on one KV
# head of 128, blocks of 64, 64 lanes of 2816 positions; d_inner 5120,
# d_state 16, chunks of 256
_G_B, _G_HQ, _G_D, _G_BS, _G_NBPS, _G_NB = 64, 20, 128, 64, 44, 2817
_G_PAGE = (_G_NB, _G_BS, _G_D)
_S_DI, _S_DS, _S_CHUNK = 5120, 16, 256


def _paged_grouped(S):
    """One KV head under 20 query heads: the step's 64 lanes."""
    return _paged._paged_core.lower(
        S((_G_B, _G_HQ, _G_D), bf16), S(_G_PAGE, bf16), S(_G_PAGE, bf16),
        S((_G_B, _G_NBPS), i32), S((_G_B,), i32), interpret=False)


def _paged_window(queries, heads, kv_heads, dk, dv, bs, nbps, nb,
                  value_scale=1.0):
    """A chunk's queries against each page of their sequence once: runs
    of whole pages, the heads cut out of them inside the kernel."""
    def lower(S):
        return _paged._window_core.lower(
            S((queries, heads, dk), bf16), S((nb, bs, kv_heads * dk), bf16),
            S((nb, bs, kv_heads * dv), bf16), S((nbps,), i32), S((), i32),
            interpret=False, value_scale=value_scale)
    return lower


def _selective_scan(N, T, nb):
    """(1, chunk) continuing one lane's row, or (lanes, 1) in place."""
    def lower(S):
        seq = (N, T, _S_DI)
        return _scan._scan_core.lower(
            S(seq, bf16), S(seq, f32), S(seq, bf16), S((N, T, _S_DS), f32),
            S((N, T, _S_DS), f32), S((_S_DS, _S_DI), f32), S((_S_DI,), f32),
            S((_G_B, _S_DS, _S_DI), f32), S((N // nb,), i32), S((N,), i32),
            nb=nb, interpret=False)
    return lower


# the routed cell's widths (mimo-v2-flash.serve-mixed): 64 query heads, keys
# 192 and values 128 wide, on 4 KV heads through block tables (full layers)
# and on 8 through a ring of 3 blocks a lane (window layers, a sink logit a
# head), blocks of 64, 96 lanes of 9,216 positions, chunks of 512; 16
# experts of width 2,048 held, top-8 of 256
_M_B, _M_HQ, _M_DK, _M_DV, _M_BS, _M_NBPS, _M_CHUNK = 96, 64, 192, 128, 64, \
    144, 512
_M_NB, _M_RING = _M_B * _M_NBPS + 1, 3
_M_NBW = _M_B * _M_RING + 1
_M_C, _M_FE, _M_E, _M_K, _M_EALL = 4096, 2048, 16, 8, 256


def _paged_wide(lanes, kv_heads, nbps, nb, window):
    """Keys wider than values; with a window a first visible position a
    lane and a sink logit a head; the value scale."""
    def lower(S):
        return _paged._paged_core_opts.lower(
            S((lanes, _M_HQ, _M_DK), bf16),
            S((nb, _M_BS, kv_heads * _M_DK), bf16),
            S((nb, _M_BS, kv_heads * _M_DV), bf16),
            S((lanes, nbps), i32), S((lanes,), i32),
            S((lanes,), i32) if window else None,
            S((_M_HQ,), f32) if window else None,
            interpret=False, value_scale=0.707)
    return lower


def _moe_experts(tokens):
    """The experts' kernel over the rows `plan` lays out for ``tokens``
    tokens (a step's 96 lanes, a chunk's 512 positions)."""
    def lower(S):
        tm = _moe.tile_rows(tokens, _M_K, _M_EALL)
        tiles = -(-tokens * _M_K // tm) + _M_E
        return _moe._experts_core.lower(
            S((tiles * tm, _M_C), bf16), S((tiles * tm, 1), f32),
            S((tiles,), i32), S((tiles,), i32),
            S((_M_E, _M_FE, _M_C), bf16), S((_M_E, _M_FE, _M_C), bf16),
            S((_M_E, _M_C, _M_FE), bf16), tm=tm, interpret=False)
    return lower


# keye-vl-2-30b-a3b.serve-longdocs: 32 query heads on 4 KV heads of 128, an
# index of 16 heads of 64 that keeps 2,048 positions, blocks of 64, 16
# lanes of 33,792 positions, chunks of 512
_K_B, _K_HQ, _K_HKV, _K_D, _K_HI, _K_DI, _K_TOPK = 16, 32, 4, 128, 16, 64, 2048
_K_BS, _K_NBPS, _K_CHUNK = 64, 528, 512
_K_NB = _K_B * _K_NBPS + 1
_K_ROW = 128                    # index_row(64): a key, then zeros


def _index_scores(seqs, queries):
    """A step's lanes of one query, or a chunk's one sequence of many."""
    def lower(S):
        return _sparse._index_core.lower(
            S((seqs, queries, _K_HI, _K_DI), bf16),
            S((seqs, queries, _K_HI), f32), S((_K_NB, _K_BS, _K_ROW), bf16),
            S((seqs, _K_NBPS), i32), S((seqs,), i32), interpret=False)
    return lower


def _paged_sparse(seqs, queries):
    def lower(S):
        pool = S((_K_NB, _K_BS, _K_HKV * _K_D), bf16)
        return _sparse._sparse_core.lower(
            S((seqs, queries, _K_HQ, _K_D), bf16), pool, pool,
            S((seqs, _K_NBPS), i32), S((seqs,), i32),
            S((seqs, queries, _K_NBPS * _K_BS), i32), interpret=False)
    return lower


def _select(rows):
    """A step's 16 lanes or a chunk's 512 queries, a row of scores of
    every position of the table."""
    def lower(S):
        return _sparse._select_core.lower(
            S((rows, _K_NBPS * _K_BS), f32), S((rows,), i32), k=_K_TOPK,
            interpret=False)
    return lower


def _flash_fwd(T, bk):
    def lower(S):
        x = S((2, 16, T, 64), bf16)
        return _flash._flash_core.lower(x, x, x, True, 0.125, 512, bk, False)
    return lower


def _flash_bwd(S):
    x = S((2, 16, 2048, 64), bf16)
    row = S((2, 16, 2048), f32)
    return _flash._flash_bwd_core.lower(x, x, x, x, row, row, True, 0.125,
                                        512, 512, False)


def _xent_fwd(V):
    return lambda S: jax.jit(_xent._pallas_fwd, static_argnums=(1, 2)).lower(
        S((4096, V), bf16), False, False)


def _xent_bwd(V):
    return lambda S: jax.jit(_xent._pallas_bwd, static_argnums=(4,)).lower(
        S((4096, V), bf16), S((4096,), i32), S((4096,), f32),
        S((4096,), f32), False)


def _dropout_mask(cols):
    def lower(S):
        br, bc = _dropout._tile_geometry(4096, cols, 2)   # bf16 activations
        fn = jax.jit(_dropout._kernel2d, static_argnums=(0, 4, 5, 6, 7, 8))
        return fn.lower((4096, cols), S((1,), i32), S((), i32), S((), i32),
                        0.1, br, bc, cols // bc, False)
    return lower


# (lowering, the `name=` of each Mosaic kernel the program must contain)
_KERNELS = {
    "paged_bf16": (_paged_bf16(), ["paged_attention"]),
    "paged_int8": (_paged_int8(), ["paged_attention_q8"]),
    "paged_bf16_cell_64_blocks": (_paged_bf16(_CELL_NBPS, _CELL_NB),
                                  ["paged_attention"]),
    "paged_int8_cell_64_blocks": (_paged_int8(_CELL_NBPS, _CELL_NB),
                                  ["paged_attention_q8"]),
    "paged_grouped_step_20q_1kv": (_paged_grouped, ["paged_attention"]),
    "paged_window_chunk_20q_1kv": (
        _paged_window(_S_CHUNK, _G_HQ, 1, _G_D, _G_D, _G_BS, _G_NBPS, _G_NB),
        ["paged_attention_window"]),
    # gpt2-medium.serve-batch's chunk: 32 queries, 16 heads of 64 side by
    # side in a row of 1,024 lanes
    "paged_window_chunk_16q_16kv_d64": (
        _paged_window(32, _H, _H, _D, _D, _BS, _CELL_NBPS, _CELL_NB),
        ["paged_attention_window"]),
    "paged_step_64q_4kv_k192_v128": (
        _paged_wide(_M_B, 4, _M_NBPS, _M_NB, False), ["paged_attention"]),
    "paged_step_64q_8kv_ring_window_sink": (
        _paged_wide(_M_B, 8, _M_RING, _M_NBW, True), ["paged_attention"]),
    "paged_chunk_lanes_64q_4kv_k192_v128": (
        _paged_wide(_M_CHUNK, 4, _M_NBPS, _M_NB, False), ["paged_attention"]),
    # mimo-v2-flash.serve-mixed's chunk in a full layer: 8,192 rows a KV
    # head in tiles, keys 192 and values 128 wide
    "paged_window_chunk_64q_4kv_k192_v128": (
        _paged_window(_M_CHUNK, _M_HQ, 4, _M_DK, _M_DV, _M_BS, _M_NBPS,
                      _M_NB, value_scale=0.707), ["paged_attention_window"]),
    "moe_experts_step_96x8_of_256": (_moe_experts(_M_B), ["moe_experts"]),
    "moe_experts_chunk_512x8_of_256": (_moe_experts(_M_CHUNK),
                                       ["moe_experts"]),
    "index_scores_step_16_lanes": (_index_scores(_K_B, 1), ["index_scores"]),
    "index_scores_chunk_512": (_index_scores(1, _K_CHUNK), ["index_scores"]),
    "select_positions_step_16_lanes": (_select(_K_B), ["select_positions"]),
    "select_positions_chunk_512": (_select(_K_CHUNK), ["select_positions"]),
    "paged_sparse_step_32q_4kv": (_paged_sparse(_K_B, 1),
                                  ["paged_attention_sparse"]),
    "paged_sparse_chunk_512x32q_4kv": (_paged_sparse(1, _K_CHUNK),
                                       ["paged_attention_sparse"]),
    "selective_scan_chunk_1x256": (_selective_scan(1, _S_CHUNK, 1),
                                   ["selective_scan"]),
    "selective_scan_step_64x1": (_selective_scan(_G_B, 1, 8),
                                 ["selective_scan"]),
    # T=2048: K/V stay VMEM-resident; T=16384: streamed over the grid
    "flash_fwd_resident_T2048": (_flash_fwd(2048, 512), ["flash_fwd"]),
    "flash_fwd_streamed_T16384": (_flash_fwd(16384, 1024),
                                  ["flash_fwd_streamed"]),
    "flash_bwd_dkdv_dq_T2048": (_flash_bwd, ["flash_bwd_dkv",
                                             "flash_bwd_dq"]),
    "xent_fwd_V30522": (_xent_fwd(30522), ["xent_fwd"]),
    "xent_bwd_V30522": (_xent_bwd(30522), ["xent_bwd"]),
    "xent_fwd_V32000": (_xent_fwd(32000), ["xent_fwd"]),
    "xent_bwd_V32000": (_xent_bwd(32000), ["xent_bwd"]),
    "dropout_mask_4096x1024": (_dropout_mask(1024), ["dropout_mask"]),
    "dropout_mask_4096x4096": (_dropout_mask(4096), ["dropout_mask"]),
}
_HLO = {}      # compiled text per kernel: both tests below read one compile


def _compiled_text(one_chip, name):
    if name not in _HLO:
        def S(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        _HLO[name] = _KERNELS[name][0](S).compile().as_text()
    return _HLO[name]


@pytest.mark.parametrize("name", list(_KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    hlo = _compiled_text(one_chip, name)
    assert hlo.count("tpu_custom_call") >= len(_KERNELS[name][1]), name


@pytest.mark.parametrize("name", list(_KERNELS))
def test_kernel_keeps_its_name_for_v5e(one_chip, name):
    """The `name=` of each `pallas_call` is the compiled instruction's
    name, which is what a device trace shows and what the benchmark's
    readers look for (`perf/metrics/*_roofline.py`)."""
    hlo = _compiled_text(one_chip, name)
    for kernel in _KERNELS[name][1]:
        calls = re.findall(rf"%{kernel}(?:\.\d+)? = [^\n]*tpu_custom_call",
                           hlo)
        assert calls, (name, kernel)


def test_pages_a_grid_step_at_the_cells_shapes():
    """`pages_per_step` at the two served cells' shapes, which the cases
    above compile with: a later change of the rule shows here.  2,048
    grid steps a call become 128, and 2,816 become 192 (44 entries a
    row padded to 48)."""
    assert _paged.pages_per_step(_BS, _CELL_NBPS, _H * _D * 2) == 16
    assert _paged.pages_per_step(_BS, _CELL_NBPS, _H * _D) == 16    # int8
    assert _paged.pages_per_step(_BS, _NBPS, _H * _D * 2) == 16     # smoke
    assert _paged.pages_per_step(_G_BS, _G_NBPS, _G_D * 2) == 16


# --- the served programs: one layout for the KV pool -------------------- #
# the benchmark cell's engine (gpt2-medium.serve-batch) at two layers
_CELL_B, _CELL_CHUNK, _CELL_L = 32, 32, 2
_CELL_PAGE = (_CELL_NB, _BS, _H * _D)
_CELL_SCALE = (_CELL_NB, _BS, _H)


@pytest.fixture(scope="module")
def cell_weights():
    """(shapes of the weight pytree, activations) of a two-layer decoder
    at the cell's widths, as `PagedPrograms` hands them to its programs."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import generation as G
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    mx.random.seed(0)
    units = _H * _D
    net = TransformerLM(vocab=512, units=units, hidden_size=4 * units,
                        num_layers=_CELL_L, num_heads=_H,
                        max_len=_CELL_NBPS * _BS, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), i32)))
    net.cast("bfloat16")
    params = G._gather_params(net, _CELL_NBPS * _BS)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    return shapes, G.decoder_spec(net)


def _served_program(program, kv_dtype, spec, B=_CELL_B, bs=_BS,
                    nbps=_CELL_NBPS, chunk=_CELL_CHUNK):
    """(the real program body, its arguments' shapes after the pools and
    the recurrent state)."""
    from incubator_mxnet_tpu.serving import programs as SP

    if program == "serving_step":
        fn = SP._build_step(spec, bs, nbps, 0.0, 0, kv_dtype, "pallas",
                            program)
        # the step before's tokens (a device array, not donated), then the
        # host's: tables, tokens, which lanes take the host's, positions,
        # live lanes, keys
        rest = [((B,), i32), ((B, nbps), i32), ((B,), i32),
                ((B,), jnp.bool_), ((B,), i32), ((B,), jnp.bool_),
                ((B, 2), jnp.uint32)]
    else:
        fn = SP._build_prefill_chunk(spec, bs, nbps, chunk, 0.0, 0,
                                     kv_dtype, "pallas", program)
        rest = [((nbps,), i32), ((chunk,), i32), ((), i32), ((), i32),
                ((2,), jnp.uint32), ((), i32)]
    return fn, rest


def _copies_of(hlo, dtype, shape):
    dims = ",".join(map(str, shape))
    return re.findall(rf"= {dtype}\[{dims}\]\S* copy\(", hlo)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["float", "kv8"])
@pytest.mark.parametrize("program", ["serving_step", "serving_prefill_chunk"])
def test_served_program_leaves_the_pool_where_it_lies(
        one_chip, monkeypatch, cell_weights, program, kv_dtype):
    """The K/V write, the paged kernel and the donated buffer agree on
    one layout of the pool (docs/serving.md, "The pool's layout"): the
    compiled program copies no K/V pool array, holds no pool-sized
    temporary, and returns every pool in its argument's buffer.  Over
    the ``(num_blocks, H, bs, D)`` pool the same compile held three
    copies of every pool array (12 here, 144 in the 24-layer cell) and
    temporaries of twice the pool."""
    shapes, spec = cell_weights
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kv8 = kv_dtype == "int8"
    pool = (S(_CELL_PAGE, i8 if kv8 else bf16),) * _CELL_L
    scale = (S(_CELL_SCALE, f32),) * _CELL_L if kv8 else ()
    fn, rest = _served_program(program, kv_dtype, spec)
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), shapes)
    compiled = jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4)).lower(
        pool, pool, scale, scale, (), *(S(*sd) for sd in rest),
        params).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == _CELL_L      # the real kernel
    # a float chunk's queries walk the pages once together; int8 pages
    # (no scales in the window form) stay lanes of the single-query kernel
    kernel = "paged_attention" + (
        "_q8" if kv8 else "_window" if program == "serving_prefill_chunk"
        else "")
    assert len(re.findall(rf"%{kernel}(?:\.\d+)? = [^\n]*tpu_custom_call",
                          hlo)) == _CELL_L, kernel
    # (a) no copy of a K/V pool array
    assert not _copies_of(hlo, "s8" if kv8 else "bf16", _CELL_PAGE)
    # the fp32 scale pools keep one copy in and one out: with 16 heads in
    # its last place the device's own layout of that array makes
    # `num_blocks` minor, and a Mosaic call takes row-major alone (the
    # array is a sixteenth of its page pool)
    assert len(_copies_of(hlo, "f32", _CELL_SCALE)) <= 2 * len(scale) * 2
    # (b) no pool-sized temporary
    page_bytes = math.prod(_CELL_PAGE) * (1 if kv8 else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < page_bytes
    # (c) every pool output aliases its argument
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    held = 2 * len(pool) + 2 * len(scale)
    for i in range(held):
        assert f"{{{i}}}: ({i}, {{}}" in aliases, (i, aliases)
    # and nothing else does: the step before's tokens are read and left
    assert len(re.findall(r"\{\d+\}: \(", aliases)) == held, aliases


# --- the hybrid cell's programs: two kinds of state, neither copied ----- #
@pytest.fixture(scope="module")
def hybrid_weights():
    """(weight shapes, spec) of three Mamba layers and one attention layer
    at the hybrid cell's published widths, as `PagedPrograms` hands them
    over: a stacked leaf a kind of weight, a row a layer."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import generation as G
    from incubator_mxnet_tpu.models.hybrid_ssm import HybridSSMDecoder

    mx.random.seed(0)
    net = HybridSSMDecoder(
        vocab_size=65536, hidden_size=2560, intermediate_size=8192,
        num_hidden_layers=4, num_attention_heads=_G_HQ,
        num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
        mamba_d_state=_S_DS, mamba_d_conv=4, mamba_expand=2,
        mamba_dt_rank=160, max_position_embeddings=_G_NBPS * _G_BS,
        dtype="bfloat16", grad_req="null")
    net.initialize(mx.init.Zero())
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        G._gather_params(net, _G_NBPS * _G_BS))
    return shapes, G.decoder_spec(net)


@pytest.mark.parametrize("program", ["serving_step", "serving_prefill_chunk"])
def test_hybrid_program_leaves_both_states_where_they_lie(
        one_chip, monkeypatch, hybrid_weights, program):
    """A decoder with recurrent layers threads a second donated state
    through the same two programs (docs/serving.md, "Two kinds of
    state").  At the published widths, for the described v5e: both
    kernels are in the program under their names, no K/V pool, recurrent
    state or conv window is copied, every one of them comes back in its
    argument's buffer, and the temporaries stay under one state array.
    The weights arrive stacked (`generation.StackedLayers`): the program
    takes two dozen weight buffers however deep the net, and reads a
    layer's row where it lies, so nothing of a matrix's size is computed
    at the program's top level but by the fusion that consumes it."""
    shapes, spec = hybrid_weights
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state, conv = (_G_B, _S_DS, _S_DI), (3, _G_B, _S_DI)
    pool = (S(_G_PAGE, bf16),)
    rec = ((S(state, f32),) * 3, (S(conv, bf16),) * 3)
    fn, rest = _served_program(program, None, spec, B=_G_B, bs=_G_BS,
                               nbps=_G_NBPS, chunk=_S_CHUNK)
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), shapes)
    compiled = jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4)).lower(
        pool, pool, (), (), rec, *(S(*sd) for sd in rest), params).compile()
    hlo = compiled.as_text()
    paged = "paged_attention" if program == "serving_step" \
        else "paged_attention_window"   # a chunk reads each page once
    for kernel in (paged, "selective_scan"):
        assert re.findall(rf"%{kernel}(?:\.\d+)? = [^\n]*tpu_custom_call",
                          hlo), kernel
    # (a copy whose layout names a memory space, `S(1)`, is the compiler
    # prefetching a small array into fast memory, not a second array)
    for dtype, shape in (("bf16", _G_PAGE), ("f32", state), ("bf16", conv)):
        assert not [c for c in _copies_of(hlo, dtype, shape)
                    if "S(" not in c], (dtype, shape)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < math.prod(state) * 4 + 2 * 65536 * _S_CHUNK * 4   # + the logits
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    for i in range(8):              # pool_k, pool_v, 3 states, 3 windows
        assert f"{{{i}}}: ({i}, {{}}" in aliases, (i, aliases)
    entry = hlo[hlo.index("ENTRY"):]
    assert len(jax.tree_util.tree_leaves(shapes)) == 22
    assert len(re.findall(r" parameter\(\d+\)", entry)) < 22 + 8 + 8
    rows = "8192,2560|2560,8192|10240,2560|2560,5120"   # a layer's matrices
    assert not re.findall(rf"= bf16\[(?:{rows})\]\S* \w[\w-]*\(", entry)


# --- the routed cell's programs: two kinds of pool, the experts' kernel -- #
@pytest.fixture(scope="module")
def routed_weights():
    """(weight shapes, spec) of the routed cell's seven layers at its
    published widths, as `PagedPrograms` hands them over: a list, a dict a
    layer.  Three layers are built (full + dense, window + routed, full +
    routed: every form the seven take) and their shapes laid out in the
    cell's order."""
    import json

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import generation as G
    from incubator_mxnet_tpu.models.routed_window import RoutedWindowDecoder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "mimo-v2-flash.json")) as f:
        cfg = json.load(f)
    kw = {k: cfg[v] for k, v in cfg["program"]["kwargs"].items()}
    kw.update(cfg["program"]["constants"], num_hidden_layers=3,
              hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0, 1, 1],
              max_position_embeddings=_M_NBPS * _M_BS)
    net = RoutedWindowDecoder(**kw)
    net.initialize(mx.init.Zero())
    three = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        G._gather_params(net, _M_NBPS * _M_BS))
    spec3 = G.decoder_spec(net)
    order = [0 if not win and not moe else 1 if win else 2
             for win, moe in zip(cfg["hybrid_layer_pattern"],
                                 cfg["moe_layer_freq"])]
    shapes = dict(three, layers=[three["layers"][i] for i in order])
    spec = spec3._replace(kinds=("attn",) * len(order),
                          acts=tuple(spec3.acts[i] for i in order),
                          attn=tuple(spec3.attn[i] for i in order))
    return shapes, spec


@pytest.mark.parametrize("program", ["serving_step", "serving_prefill_chunk"])
def test_routed_program_leaves_both_kinds_of_pool_where_they_lie(
        one_chip, monkeypatch, routed_weights, program):
    """A decoder with window layers and routed experts through the same
    two programs (docs/serving.md, "Window layers and routed experts"), at
    the cell's widths and depth for the described v5e: the single-query
    kernel is there for every attention layer of the step and the window
    form for the chunk's two full layers (a window layer's chunk attends
    its own keys densely), the experts' kernel for each of the six routed
    layers; no
    pool array of either kind is copied (the block tables' 13,825 blocks,
    the rings' 289) and every one comes back in its argument's buffer, the
    experts' counts with them; the temporaries stay under the chunk's
    logits and a layer's scores."""
    shapes, spec = routed_weights
    assert spec.window == 128 and spec.moe.held == _M_E
    assert [a.kv_heads for a in spec.attn] == [4, 8, 8, 8, 8, 4, 8]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pools(d):
        return tuple(S((_M_NBW if a.window else _M_NB, _M_BS,
                        a.kv_heads * d), bf16) for a in spec.attn)

    fn, rest = _served_program(program, None, spec, B=_M_B, bs=_M_BS,
                               nbps=_M_NBPS, chunk=_M_CHUNK)
    if program == "serving_step":       # the counts follow the tokens
        rest[0] = ((_M_B + 3,), i32)
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), shapes)
    compiled = jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4)).lower(
        pools(_M_DK), pools(_M_DV), (), (), (S((3,), i32),),
        *(S(*sd) for sd in rest), params).compile()
    hlo = compiled.as_text()

    def calls(kernel):
        return len(re.findall(
            rf"%{kernel}(?:\.\d+)? = [^\n]*tpu_custom_call", hlo))

    assert calls("moe_experts") == 6
    # the chunk's two full layers walk their pages once for all 512
    # queries (a window layer's chunk attends its own keys densely)
    assert (calls("paged_attention"), calls("paged_attention_window")) == (
        (7, 0) if program == "serving_step" else (0, 2))
    for shape in ((_M_NB, _M_BS, 4 * _M_DK), (_M_NB, _M_BS, 4 * _M_DV),
                  (_M_NBW, _M_BS, 8 * _M_DK), (_M_NBW, _M_BS, 8 * _M_DV)):
        assert not [c for c in _copies_of(hlo, "bf16", shape)
                    if "S(" not in c], shape
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    for i in range(15):             # 7 key pools, 7 value pools, the counts
        assert f"{{{i}}}: ({i}, {{}}" in aliases, (i, aliases)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        32 if program == "serving_step" else 96) * 2 ** 20
    # a layer's leaves are the net's own buffers, parameters of the
    # program as they are: nothing of a matrix's size is computed at the
    # program's top level (no copy, no transpose, no stack)
    assert len(jax.tree_util.tree_leaves(shapes)) == 7 * 6 + 5 + 3 + 6 * 5 + 3
    entry = hlo[hlo.index("ENTRY"):]
    rows = "12288,4096|4096,8192|16384,4096|16,2048,4096|16,4096,2048"
    # (a layout that names a memory space, `S(1)`, is the compiler's
    # prefetch of a matrix into fast memory, not a second array in HBM)
    assert not [m for m in re.findall(
        rf"= bf16\[(?:{rows})\]\S* (?!parameter\()\w[\w-]*\(", entry)
        if "S(" not in m]


# --- the sparse cell's programs: three pools a layer ------------------------ #
@pytest.fixture(scope="module")
def sparse_weights():
    """(weight shapes, spec) of the sparse cell's eight layers at their
    published widths, as `PagedPrograms` hands them over.  One layer is
    built (every layer is of one kind) and its shapes laid out eight
    times."""
    import json

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import generation as G
    from incubator_mxnet_tpu.models.routed_sparse import RoutedSparseDecoder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "keye-vl-2-30b-a3b.json")) as f:
        cfg = json.load(f)
    kw = {k: cfg[v] for k, v in cfg["program"]["kwargs"].items()}
    kw.update(cfg["program"]["constants"], num_hidden_layers=1,
              max_position_embeddings=_K_NBPS * _K_BS)
    net = RoutedSparseDecoder(**kw)
    net.initialize(mx.init.Zero())
    one = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        G._gather_params(net, _K_NBPS * _K_BS))
    L = cfg["num_hidden_layers"]
    spec1 = G.decoder_spec(net)
    spec = spec1._replace(kinds=("attn",) * L, acts=("routed",) * L,
                          attn=spec1.attn * L)
    return dict(one, layers=[one["layers"][0]] * L), spec


@pytest.mark.parametrize("program", ["serving_step", "serving_prefill_chunk"])
def test_sparse_program_leaves_all_three_pools_where_they_lie(
        one_chip, monkeypatch, sparse_weights, program):
    """A decoder with an index through the same two programs
    (docs/serving.md, "An index over the pages"), at the cell's widths and
    depth for the described v5e: the index-scores kernel, the selection
    kernel and the sparse attention kernel are there once a layer in both
    programs, the experts' kernel likewise; no pool array of the three
    kinds is copied (8,449 blocks of K, V and index keys) and every one
    comes back in its argument's buffer, the counts with them; the
    temporaries stay under a chunk's scores and masks."""
    from incubator_mxnet_tpu.serving import programs as SP

    shapes, spec = sparse_weights
    assert spec.index == (_K_HI, _K_DI, _K_TOPK) and spec.moe.held == 16
    assert spec.moe.scoring == "softmax" and len(spec.attn) == 8
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pools(width):
        return tuple(S((_K_NB, _K_BS, width), bf16) for _ in spec.attn)

    fn, rest = _served_program(program, None, spec, B=_K_B, bs=_K_BS,
                               nbps=_K_NBPS, chunk=_K_CHUNK)
    n_counts = SP.counts_carried(spec)
    assert n_counts == 7
    if program == "serving_step":       # the counts follow the tokens
        rest[0] = ((_K_B + n_counts,), i32)
    params = jax.tree_util.tree_map(lambda a: S(a.shape, a.dtype), shapes)
    compiled = jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4)).lower(
        pools(_K_HKV * _K_D), pools(_K_HKV * _K_D), (), (),
        (S((n_counts,), i32), pools(_K_ROW)),
        *(S(*sd) for sd in rest), params).compile()
    hlo = compiled.as_text()

    def calls(kernel):
        return len(re.findall(
            rf"%{kernel}(?:\.\d+)? = [^\n]*tpu_custom_call", hlo))

    assert (calls("index_scores"), calls("select_positions"),
            calls("paged_attention_sparse"), calls("moe_experts")) == (
                8, 8, 8, 8)
    assert calls("paged_attention") == calls("paged_attention_window") == 0
    for width in (_K_HKV * _K_D, _K_ROW):
        assert not [c for c in _copies_of(hlo, "bf16",
                                          (_K_NB, _K_BS, width))
                    if "S(" not in c], width
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    for i in range(25):     # 8 key pools, 8 value pools, counts, 8 index
        assert f"{{{i}}}: ({i}, {{}}" in aliases, (i, aliases)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        64 if program == "serving_step" else 320) * 2 ** 20


# --- the same kernels in a program traced over the 2x2 mesh ------------- #
def _mesh_xent(x, labels):
    def loss(x):
        return jnp.mean(_xent.fused_sparse_xent(x, labels))
    return jax.value_and_grad(loss)(x)


def _mesh_flash(q):
    def loss(q):
        return jnp.sum(_flash.flash_attention(q, q, q, causal=True)
                       .astype(f32))
    return jax.value_and_grad(loss)(q)


def _mesh_dropout(x, seed):
    return _dropout.fused_dropout(x, seed, 0.1)


def _mesh_paged(q, pool, tables, pos):
    return _paged.paged_attention(q, pool, pool, tables, pos, impl="pallas")


def _mesh_paged_ring(q, pool_k, pool_v, tables, pos, sink):
    return _paged.paged_attention(q, pool_k, pool_v, tables, pos,
                                  first=pos % _M_BS, sink=sink,
                                  value_scale=0.707, impl="pallas")


def _mesh_window(q, pool_k, pool_v, row, start):
    return _paged.paged_attention_window(q, pool_k, pool_v, row, start,
                                         value_scale=0.707)


def _mesh_index(qi, w, pool, tables, last):
    return _sparse.index_scores(qi, w, pool, tables, last, impl="pallas")


def _mesh_select(scores, pos):
    return _sparse.select_positions(scores, pos, _K_TOPK, impl="pallas")


def _mesh_sparse(q, pool_k, pool_v, tables, last, seen):
    return _sparse.paged_attention_sparse(q, pool_k, pool_v, tables, last,
                                          seen, impl="pallas")


def _mesh_moe(x, idx, wts, gate, up, down):
    return _moe.routed_experts(x, idx, wts, jnp.ones(x.shape[:1], bool),
                               gate, up, down, experts=_M_EALL,
                               impl="pallas")


# (function, (shape, dtype, spec) per argument, Mosaic kernels it must hold)
_MESH_PROGRAMS = {
    # BERT-large MLM logits, vocab-sharded as the TP rules leave them
    "xent_fwd_bwd_V30522": (_mesh_xent, [((4096, 30522), bf16,
                                          P("data", "model")),
                                         ((4096,), i32, P("data"))], 2),
    "dropout_mask_4096x4096": (_mesh_dropout, [((4096, 4096), bf16,
                                                P("data", "model")),
                                               ((1,), i32, P())], 1),
    "flash_fwd_bwd_T2048": (_mesh_flash, [((8, 16, 2048, 64), bf16,
                                           P("data", "model"))], 3),
    "paged_bf16": (_mesh_paged, [((_B, _H, _D), bf16, P()),
                                 (_PAGE, bf16, P()),
                                 ((_B, _NBPS), i32, P()),
                                 ((_B,), i32, P())], 1),
    # a window layer's step: lanes over `data`, the 8 KV heads over `model`
    "paged_ring_window_sink": (_mesh_paged_ring, [
        ((_M_B, _M_HQ, _M_DK), bf16, P()),
        ((_M_NBW, _M_BS, 8 * _M_DK), bf16, P()),
        ((_M_NBW, _M_BS, 8 * _M_DV), bf16, P()),
        ((_M_B, _M_RING), i32, P()), ((_M_B,), i32, P()),
        ((_M_HQ,), f32, P())], 1),
    # a chunk's window form: the KV heads over `model`, a shard's a
    # contiguous run of a page's lanes (8 of gpt2-medium's 16 heads of 64;
    # 2 of mimo-v2-flash's 4, keys 192 and values 128 wide)
    "paged_window_16kv_d64": (_mesh_window, [
        ((32, _H, _D), bf16, P()), ((_CELL_NB,) + _PAGE[1:], bf16, P()),
        ((_CELL_NB,) + _PAGE[1:], bf16, P()), ((_CELL_NBPS,), i32, P()),
        ((), i32, P())], 1),
    "paged_window_4kv_k192_v128": (_mesh_window, [
        ((_M_CHUNK, _M_HQ, _M_DK), bf16, P()),
        ((_M_NB, _M_BS, 4 * _M_DK), bf16, P()),
        ((_M_NB, _M_BS, 4 * _M_DV), bf16, P()),
        ((_M_NBPS,), i32, P()), ((), i32, P())], 1),
    # the sparse cell's step: the 16 lanes over `data` in both kernels, the
    # 4 KV heads of 128 over `model` in the attention
    "index_scores_step": (_mesh_index, [
        ((_K_B, 1, _K_HI, _K_DI), bf16, P()), ((_K_B, 1, _K_HI), f32, P()),
        ((_K_NB, _K_BS, _K_ROW), bf16, P()), ((_K_B, _K_NBPS), i32, P()),
        ((_K_B,), i32, P())], 1),
    "paged_sparse_step": (_mesh_sparse, [
        ((_K_B, 1, _K_HQ, _K_D), bf16, P()),
        ((_K_NB, _K_BS, _K_HKV * _K_D), bf16, P()),
        ((_K_NB, _K_BS, _K_HKV * _K_D), bf16, P()),
        ((_K_B, _K_NBPS), i32, P()), ((_K_B,), i32, P()),
        ((_K_B, 1, _K_NBPS * _K_BS), i32, P())], 1),
    "select_positions_step": (_mesh_select, [
        ((_K_B, 1, _K_NBPS * _K_BS), f32, P()), ((_K_B, 1), i32, P())], 1),
    # a step's experts: the 64 tiles of rows over both axes
    "moe_experts_step": (_mesh_moe, [
        ((_M_B, _M_C), bf16, P()), ((_M_B, _M_K), i32, P()),
        ((_M_B, _M_K), f32, P()), ((_M_E, _M_FE, _M_C), bf16, P()),
        ((_M_E, _M_FE, _M_C), bf16, P()),
        ((_M_E, _M_C, _M_FE), bf16, P())], 1),
}


@pytest.mark.parametrize("name", list(_MESH_PROGRAMS))
def test_kernel_compiles_per_shard_for_four_v5e(v5e, monkeypatch, name):
    fn, args, n_kernels = _MESH_PROGRAMS[name]
    mesh = Mesh(onp.array(v5e).reshape(2, 2), ("data", "model"))
    # the kernel gates ask jax for the backend and would take their CPU
    # branch here: steer them, in the test only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def traced(*a):
        with mosaic.mesh_context(mesh):
            return fn(*a)

    avals = [jax.ShapeDtypeStruct(shape, dtype,
                                  sharding=NamedSharding(mesh, spec))
             for shape, dtype, spec in args]
    hlo = jax.jit(traced).lower(*avals).compile().as_text()
    assert hlo.count("tpu_custom_call") >= n_kernels, name
    assert "CustomSPMDPartitioning" not in hlo


def test_kernels_compile_inside_a_callers_manual_region(v5e, monkeypatch):
    """The ZeRO-1 explicit tier's shape of things: the step runs inside
    the Trainer's own `shard_map` over `data`, no mesh is put in context,
    and each shard's dropout and cross-entropy kernels are called as they
    are."""
    mesh = Mesh(onp.array(v5e), ("data",))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def body(x, labels, seed):
        def loss(x):
            x = _dropout.fused_dropout(x, seed, 0.1)
            return jnp.mean(_xent.fused_sparse_xent(x, labels))
        return jax.lax.pmean(jax.grad(loss)(x), "data")

    step = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data"), P()),
                         out_specs=P("data"), check_vma=False)
    avals = [jax.ShapeDtypeStruct(shape, dtype,
                                  sharding=NamedSharding(mesh, spec))
             for shape, dtype, spec in [((4096, 30522), bf16, P("data")),
                                        ((4096,), i32, P("data")),
                                        ((1,), i32, P())]]
    hlo = jax.jit(step).lower(*avals).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 3      # mask, xent fwd, xent bwd


def test_int8_dot_moves_fewer_bytes_than_float(one_chip):
    """The ordering the int8 decode programs rely on: the TPU program of
    an int8-weight mixed dot is charged fewer bytes than the bf16 dot of
    the same shape.  Asked of the v5e compiler, not of XLA:CPU — the CPU
    backend upcasts the int8 operand into a temporary and charges that
    too, which says nothing about the accelerator program."""
    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def bytes_accessed(wdtype):
        x = jax.ShapeDtypeStruct((8, 256), bf16, sharding=one_chip)
        w = jax.ShapeDtypeStruct((256, 256), wdtype, sharding=one_chip)
        cost = jax.jit(dot).lower(x, w).compile().cost_analysis()
        return cost["bytes accessed"]

    assert bytes_accessed(i8) < bytes_accessed(bf16)
