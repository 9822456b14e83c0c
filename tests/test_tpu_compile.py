"""What the chip's compiler accepts, checked without the chip.

The TPU compiler is installed here and compiles for a v5e that is described,
not attached (``on-chip-measurement`` guide, section 2): every Pallas kernel
of the train and serve main paths at real widths, and the data-parallel train
step on the 2x2 mesh. Interpret mode on the CPU cannot see what these see — a
block not aligned to the (8,128) tiling, a scalar store to VMEM, a kernel
GSPMD cannot partition — and each of those stopped a program before its
first chip run.

The topology is described inside a module fixture, never at import (one
process at a time may load the TPU library; see the guide), every compile
runs in this process, and JAX's persistent cache is off around them: a
compile for a described chip is written to it but cannot be read back.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ray_lightning_tpu.models.llama import (
    LlamaConfig,
    init_params,
    lm_loss,
    shardings_for_mesh,
)
from ray_lightning_tpu.ops import paged_attention as pa
from ray_lightning_tpu.ops.attention import attention
from ray_lightning_tpu.ops.rmsnorm import _rmsnorm_pallas
from ray_lightning_tpu.serving.engine import prefill_rungs
from tests.utils import pallas_calls as _pallas_calls

SMALL = LlamaConfig.small()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _custom_calls(fn, *shapes, sharding):
    """Compile ``fn`` for the described chip; count its Mosaic kernels."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


# q [B, Hq, S, hd] / kv [B, Hkv, S, hd] of LlamaConfig.small() at batch 8
_Q = ((8, SMALL.n_heads, SMALL.max_seq, SMALL.head_dim), jnp.bfloat16)
_KV = ((8, SMALL.n_kv_heads, SMALL.max_seq, SMALL.head_dim), jnp.bfloat16)


# serve-swa-moe-doc at its 16,384 rung (benchmarks/configs/command-a-plus-d4-e16.json)
_DOC_QKV = (
    ((1, 128, 16384, 128), jnp.bfloat16),
    ((1, 8, 16384, 128), jnp.bfloat16),
    ((1, 8, 16384, 128), jnp.bfloat16),
)


# train-dense-4k: four sequences of 4,096 (benchmarks/configs/mistral-7b-d4.json)
_TRAIN_QKV = (
    ((4, 32, 4096, 128), jnp.bfloat16),
    ((4, 8, 4096, 128), jnp.bfloat16),
    ((4, 8, 4096, 128), jnp.bfloat16),
)
# serve-mla-moe-reason at its 2,048 rung: keys of 192 columns, values of 128
_LATENT_QKV = (
    ((1, 32, 2048, 192), jnp.bfloat16),
    ((1, 32, 2048, 192), jnp.bfloat16),
    ((1, 32, 2048, 128), jnp.bfloat16),
)
# serve-ssm-chat at its 2,048 rung: 20 query heads over one key/value head
_SSM_QKV = (
    ((1, 20, 2048, 128), jnp.bfloat16),
    ((1, 1, 2048, 128), jnp.bfloat16),
    ((1, 1, 2048, 128), jnp.bfloat16),
)


def _flash(window=None, **blocks):
    return lambda q, k, v: attention(
        q, k, v, causal=True, impl="flash", interpret=False, window=window,
        **blocks
    )


def _flash_grads(**blocks):
    return jax.grad(
        lambda q, k, v: _flash(**blocks)(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))


_TILE_512 = dict(block_q=512, block_k=512)


@pytest.mark.parametrize(
    "name,fn,shapes,kernels",
    [
        ("fwd", _flash(), (_Q, _KV, _KV), 1),
        ("fwd+bwd", _flash_grads(), (_Q, _KV, _KV), 3),  # forward, dQ, dK/dV
        ("window", _flash(window=512), (_Q, _KV, _KV), 1),
        # head_dim 64 is padded to the 128-lane tile inside the op
        ("head_dim64", _flash(), (((8, 12, 512, 64), jnp.bfloat16),) * 3, 1),
        # the doc cell's longest rung: 128 heads over 8 key/value heads, at the
        # tile `_fwd_tile` picks for it a window layer's 70 pairs and the full
        # layer's 136 in scalar memory
        ("doc_window", _flash(window=4096), _DOC_QKV, 1),
        ("doc_full", _flash(), _DOC_QKV, 1),
        # all three kernels at the picked 1,024 x 1,024 at the train cell's
        # shape and at the widest rows the rules give it: the latent family's
        # padded keys, float32 heads of 128 columns
        ("train_fwd+bwd", _flash_grads(), _TRAIN_QKV, 3),
        ("latent", _flash(), _LATENT_QKV, 1),
        ("latent_fwd+bwd", _flash_grads(), _LATENT_QKV, 3),
        ("ssm", _flash(), _SSM_QKV, 1),
        ("float32_fwd+bwd", _flash_grads(),
         tuple((shape, jnp.float32) for shape, _ in _TRAIN_QKV), 3),
        # past them the rule keeps 512 x 512: the compiler refuses the wide
        # tile beside float32 rows of 256 columns (VMEM)
        ("float32_256", _flash(), (((1, 8, 2048, 256), jnp.float32),) * 3, 1),
        # the longest schedule of 512 x 512 pairs that rides as it is: 32,896
        # pairs, 386 KiB of the core's 1 MiB of scalar memory; one q tile more
        # a side and the forward pass walks the rectangular grid
        ("pairs_32896", _flash(**_TILE_512), (((1, 1, 131072, 128), jnp.bfloat16),) * 3, 1),
        ("pairs_43956", _flash(**_TILE_512), (((1, 1, 151552, 128), jnp.bfloat16),) * 3, 1),
    ],
    ids=["fwd", "fwd+bwd", "window", "head_dim64", "doc_window", "doc_full",
         "train_fwd+bwd", "latent", "latent_fwd+bwd", "ssm",
         "float32_fwd+bwd", "float32_256",
         "pairs_32896", "pairs_43956"],
)
def test_flash_attention_compiles(one_chip, name, fn, shapes, kernels):
    assert _custom_calls(fn, *shapes, sharding=one_chip) == kernels


# explicit (block_q, block_k) a caller may pass in place of the pass's own tile
EXPLICIT_TILES = ((512, 512), (512, 256), (256, 512), (256, 256))


@pytest.mark.parametrize(
    "blocks", EXPLICIT_TILES, ids=lambda b: f"{b[0]}x{b[1]}"
)
def test_autotune_block_candidates_compile(one_chip, blocks):
    """A caller's explicit tile reaches all three kernels, square or not:
    the chip's compiler takes forward and backward at each."""
    def loss(q, k, v):
        return attention(
            q, k, v, causal=True, impl="flash", interpret=False,
            block_q=blocks[0], block_k=blocks[1],
        ).astype(jnp.float32).sum()

    n = _custom_calls(
        jax.grad(loss, argnums=(0, 1, 2)), _Q, _KV, _KV, sharding=one_chip
    )
    assert n == 3


@pytest.mark.parametrize(
    "shape", [(8 * 2048, 2048), (8, 2048, 2048)], ids=["2d", "3d"]
)
def test_rmsnorm_compiles(one_chip, shape):
    n = _custom_calls(
        lambda x, w: _rmsnorm_pallas(x, w, 1e-6),
        (shape, jnp.bfloat16), ((shape[-1],), jnp.bfloat16), sharding=one_chip,
    )
    assert n == 1


def _paged_shapes(slots, hkv, group, hd, block_size, pages, columns,
                  q_dtype=jnp.bfloat16):
    """q, K pool, V pool, block table, positions of one decode call."""
    pool = ((pages, hkv, block_size, hd), jnp.bfloat16)
    return (
        ((slots, hkv, group, hd), q_dtype), pool, pool,
        ((slots, columns), jnp.int32), ((slots,), jnp.int32),
    )


_SMALL_HEADS = (SMALL.n_kv_heads, SMALL.n_heads // SMALL.n_kv_heads, SMALL.head_dim)
# what the serve cells hand the kernel (benchmarks/workloads/serve-*.json,
# benchmarks/configs/): 32 slots, 8 KV heads of 4 queries and 128 wide as
# float32, bf16 pools of 2401 (chat) and 1281 (batch) pages of 16,
# max_len 2560 = 160 table columns
_CELL_PAGED = {
    "chat-cell": _paged_shapes(32, 8, 4, 128, 16, 2401, 160, jnp.float32),
    "batch-cell": _paged_shapes(32, 8, 4, 128, 16, 1281, 160, jnp.float32),
}
_PAGED_SHAPES = {
    "small-16": _paged_shapes(8, *_SMALL_HEADS, 16, 1024, 2048 // 16),
    "small-128": _paged_shapes(8, *_SMALL_HEADS, 128, 1024, 2048 // 128),
    **_CELL_PAGED,
}


def _paged_decode(q, k, v, bt, pos):
    return pa.paged_decode_attention(q, k, v, bt, pos, interpret=False)


@pytest.mark.parametrize("shapes", list(_PAGED_SHAPES), ids=list(_PAGED_SHAPES))
def test_paged_decode_attention_compiles(one_chip, shapes):
    n = _custom_calls(_paged_decode, *_PAGED_SHAPES[shapes], sharding=one_chip)
    assert n == 1


@pytest.mark.parametrize("shapes", list(_CELL_PAGED), ids=list(_CELL_PAGED))
def test_paged_decode_attention_grid_and_scratch_at_the_cells_shapes(
    one_chip, shapes
):
    """The call as it is lowered at the serve cells' shapes: a grid of at
    most 1,000 steps a layer (the grid of one step per (row, head, page)
    had 32 x 8 x 160 = 40,960, most of them dead), and VMEM scratch that
    fits the v5e's default scoped limit of 16 MiB beside the pipelined
    query and output blocks, with no ``vmem_limit_bytes`` raised. The
    compile of these shapes for the described chip (the test above) is
    what holds the kernel to the limit; the sum says by how much."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in _CELL_PAGED[shapes]
    ]
    (call,) = _pallas_calls(jax.make_jaxpr(_paged_decode)(*args).jaxpr)
    mapping = call.params["grid_mapping"]
    assert call.params["name"] == "paged_decode_attention"
    assert int(np.prod(mapping.grid)) <= 1000
    assert call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes is None
    vmem = sum(
        int(np.prod(ref.shape)) * ref.dtype.itemsize
        for ref in mapping.scratch_avals
        if "vmem" in str(ref.memory_space).lower()
    )
    _, hkv, _, hd = _CELL_PAGED[shapes][0][0]
    blocks = 2 * 2 * hkv * 16 * hd * 4  # q and out blocks, double-buffered
    assert 0 < vmem and vmem + blocks <= 8 * 2 ** 20  # half the 16 MiB limit


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [32000, 128256])
@pytest.mark.parametrize("rows", [8, 32], ids=["slots", "slots_x_k"])
def test_greedy_sampler_compiles(one_chip, rows, vocab, dtype):
    """[num_slots, V] and the speculative verify's [num_slots*K, V]; neither
    vocab is a multiple of the kernel's block (the ragged tail is masked)."""
    n = _custom_calls(
        lambda x: pa.fused_greedy_sample(x, interpret=False),
        ((rows, vocab), dtype), sharding=one_chip,
    )
    assert n == 1


def test_single_row_sampler_compiles(one_chip):
    n = _custom_calls(
        lambda x: pa.fused_greedy_sample(x, interpret=False),
        ((1, 32000), jnp.float32), sharding=one_chip,
    )
    assert n == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [32000, 128256])
def test_temperature_sampler_compiles(one_chip, vocab, dtype):
    """The gumbel noise is drawn in the logits' dtype, as ``categorical``
    draws it, so bf16 is a program of its own."""
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    logits = jax.ShapeDtypeStruct((8, vocab), dtype, sharding=one_chip)
    text = (
        jax.jit(lambda x, k: pa.fused_sample(x, k, 0.8, interpret=False))
        .lower(logits, key).compile().as_text()
    )
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize(
    "axes",
    [{"dp": 4}, {"dp": 2, "tp": 2}, {"fsdp": 4}, {"dp": 2, "sp": 2}],
    ids=["dp4", "dp2-tp2", "fsdp4", "dp2-sp2"],
)
def test_sharded_train_step_compiles_on_the_mesh(topo, monkeypatch, axes):
    """``lm_loss`` and its gradient at small's widths (depth cut to 2) over
    the four chips, parameters placed as ``shardings_for_mesh`` places them.
    The ops (``ops/attention.py``, ``ops/rmsnorm.py``) ask ``jax.devices()``
    whether their kernels are native, so the test answers with the described
    chips; the model wraps them per shard on every mesh and asks nothing.
    GSPMD cannot partition a Mosaic kernel: without the wrap in
    ``models/llama.py`` this compile is refused."""
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    cfg = dataclasses.replace(SMALL, n_layers=2)
    mesh = Mesh(np.array(topo.devices).reshape(tuple(axes.values())), tuple(axes))
    params = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0)),
        shardings_for_mesh(cfg, mesh),
    )
    rows = tuple(a for a in ("dp", "fsdp") if a in axes)
    tokens = jax.ShapeDtypeStruct(
        (32, cfg.max_seq), jnp.int32,
        sharding=NamedSharding(mesh, P(rows, "sp" if "sp" in axes else None)),
    )
    text = (
        jax.jit(jax.grad(lambda p, t: lm_loss(p, t, cfg, mesh)[0]))
        .lower(params, tokens).compile().as_text()
    )
    assert text.count("tpu_custom_call") > 0
    assert "all-reduce" in text or "reduce-scatter" in text  # grads cross chips


# --------------------------------------------------------------------- #
# names that reach the device trace: a kernel's custom call is the HLO
# instruction %<name>.N whatever scope it was traced in, and that
# instruction's name is what a trace's XLA Ops line shows
# --------------------------------------------------------------------- #
def _kernel_instructions(text):
    """Kernels of the compiled program's Mosaic custom calls, as the
    benchmark reads them off an instruction's name."""
    import re

    from benchmarks.program_trace import kernel_of

    return sorted(
        kernel_of("custom-call %" + m.group(1))
        for m in re.finditer(
            r"%(\S+) = [^\n]*custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"",
            text,
        )
    )


def _compiled_text(fn, *shapes, sharding):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_loss(q, k, v):
    return _flash()(q, k, v).astype(jnp.float32).sum()


def _in_a_closure_named_wrapped(fn):
    def wrapped(*args):
        return fn(*args)

    return wrapped


_PAGED = (
    ((8, 4, 2, 128), jnp.bfloat16),
    ((256, 4, 16, 128), jnp.bfloat16),
    ((256, 4, 16, 128), jnp.bfloat16),
    ((8, 16), jnp.int32),
    ((8,), jnp.int32),
)


@pytest.mark.parametrize(
    "fn,shapes,names",
    [
        (_flash(), (_Q, _KV, _KV), ["flash_fwd"]),
        (
            # no checkpoint round the kernels: the compiler wraps the names
            # in the transformations (%jvp_flash_fwd_.1), which kernel_of
            # takes off again
            jax.grad(_flash_loss, argnums=(0, 1, 2)),
            (_Q, _KV, _KV),
            ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"],
        ),
        (
            jax.grad(jax.checkpoint(_flash_loss), argnums=(0, 1, 2)),
            (_Q, _KV, _KV),
            ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"],
        ),
        (
            _in_a_closure_named_wrapped(jax.jit(_flash())),
            (_Q, _KV, _KV),
            ["flash_fwd"],
        ),
        (
            lambda x, w: _rmsnorm_pallas(x, w, 1e-6),
            (((8 * 2048, 2048), jnp.bfloat16), ((2048,), jnp.bfloat16)),
            ["rmsnorm"],
        ),
        (
            lambda q, k, v, bt, pos: pa.paged_decode_attention(
                q, k, v, bt, pos, interpret=False
            ),
            _PAGED,
            ["paged_decode_attention"],
        ),
        (
            lambda x: pa.fused_greedy_sample(x, interpret=False),
            (((8, 32000), jnp.float32),),
            ["fused_argmax"],
        ),
    ],
    ids=["flash_fwd", "flash_bwd", "checkpoint", "inner_jit_in_wrapped",
         "rmsnorm", "paged_decode", "argmax"],
)
def test_kernels_keep_their_names_in_the_compiled_program(
    one_chip, fn, shapes, names
):
    text = _compiled_text(fn, *shapes, sharding=one_chip)
    assert _kernel_instructions(text) == names


def test_temperature_sampler_is_named_fused_sample(one_chip):
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    logits = jax.ShapeDtypeStruct((8, 32000), jnp.float32, sharding=one_chip)
    text = (
        jax.jit(lambda x, k: pa.fused_sample(x, k, 0.8, interpret=False))
        .lower(logits, key).compile().as_text()
    )
    assert _kernel_instructions(text) == ["fused_sample"]


def test_engine_programs_carry_their_labels_and_kernel_names(
    one_chip, topo, monkeypatch
):
    """The paged engine's two programs compiled for the chip are the modules
    ``jit_serve_prefill`` / ``jit_serve_decode``, and the decode program's
    Mosaic calls are the named kernels: once a layer the paged attention,
    the norms, the sampler. The Llama family's prefill program steps the
    decode rows too (PR 43), so it holds those and, where the prompt's shape
    lets it, ``flash_fwd``. The engine is built on the CPU with the kernel
    knob forced on; the ops ask ``jax.devices()`` at trace time whether to
    interpret, so the test answers with the described chip there."""
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    monkeypatch.setenv("RLT_PAGED_KERNEL", "1")
    cfg = LlamaConfig(
        vocab_size=2048, dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
        ffn_dim=512, max_seq=256, remat=False,
    )
    engine = InferenceEngine(
        init_params(jax.random.key(0), cfg), cfg,
        EngineConfig(num_slots=4, max_prompt_len=64, max_len=128),
    )
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    texts = {}
    for name, fn, args in engine._program_specs():
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            args,
        )
        texts[name] = fn.lower(*shapes).compile().as_text()
    assert texts["serve_prefill"].startswith("HloModule jit_serve_prefill")
    assert texts["serve_decode"].startswith("HloModule jit_serve_decode")
    decode = _kernel_instructions(texts["serve_decode"])
    assert set(decode) == {"paged_decode_attention", "rmsnorm", "fused_argmax"}
    assert "wrapped" not in " ".join(decode)
    prefill = set(_kernel_instructions(texts["serve_prefill"]))
    assert set(decode) <= prefill <= set(decode) | {"flash_fwd"}


# ---------------------------------------------------------------------- #
# what the latent-attention MoE cell hands its kernels
# (benchmarks/configs/joyai-flash-d5.json, workloads/serve-mla-moe-reason.json)
# ---------------------------------------------------------------------- #
def test_latent_paged_decode_attention_compiles_at_the_cells_shapes(one_chip):
    """64 slots of 32 heads, a latent row of 576 values in 640 lanes, bf16
    pool of 8193 pages of 16, max_len 4096 = 256 table columns."""
    def call(q, kv, bt, pos):
        return pa.mla_paged_decode_attention(
            q, kv, bt, pos, v_width=512, sm_scale=192 ** -0.5, interpret=False)

    n = _custom_calls(
        call, ((64, 32, 640), jnp.bfloat16), ((8193, 16, 640), jnp.bfloat16),
        ((64, 256), jnp.int32), ((64,), jnp.int32), sharding=one_chip)
    assert n == 1


def test_a_576_wide_latent_page_is_what_the_chip_refuses(one_chip):
    """Why the pool's row is padded to whole lanes: the chip lays a 576-wide
    array out 640 wide and refuses the copy of a 576-wide slab out of it."""
    def call(q, kv, bt, pos):
        return pa.mla_paged_decode_attention(
            q, kv, bt, pos, v_width=512, sm_scale=192 ** -0.5, interpret=False)

    with pytest.raises(Exception, match="aligned to tiling"):
        _custom_calls(
            call, ((64, 32, 576), jnp.bfloat16), ((8193, 16, 576), jnp.bfloat16),
            ((64, 256), jnp.int32), ((64,), jnp.int32), sharding=one_chip)


# what the document cell hands the kernel: 32 slots, 8 KV heads of 16 queries
# and 128 wide as float32, max_len 17408 = 1088 table columns; a full layer's
# pool of 16385 pages and the three window layers' of 3 x 8225, with each
# row's first live position
_DOC_PAGED = {
    "doc-cell-full": _paged_shapes(32, 8, 16, 128, 16, 16385, 1088, jnp.float32),
    "doc-cell-window": _paged_shapes(32, 8, 16, 128, 16, 3 * 8225, 1088, jnp.float32)
    + (((32,), jnp.int32),),
}


@pytest.mark.parametrize("shapes", list(_DOC_PAGED), ids=list(_DOC_PAGED))
def test_windowed_paged_decode_attention_compiles_at_the_cells_shapes(one_chip, shapes):
    """The kernel with a first live position a row (a window layer) and
    without (a full layer), 16 queries a KV head: one Mosaic call each, and
    the windowed one takes a third scalar-prefetch operand."""
    def call(q, k, v, bt, pos, first=None):
        return pa.paged_decode_attention(q, k, v, bt, pos, first=first, interpret=False)

    assert _custom_calls(call, *_DOC_PAGED[shapes], sharding=one_chip) == 1
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in _DOC_PAGED[shapes]]
    (kernel,) = _pallas_calls(jax.make_jaxpr(call)(*args).jaxpr)
    assert kernel.params["name"] == "paged_decode_attention"
    assert kernel.params["grid_mapping"].num_index_operands == len(args) - 3


@pytest.mark.parametrize("rows", [256, 16384], ids=["decode-tick", "prefill-chunk"])
def test_routed_expert_matmuls_compile_at_4096_square(one_chip, rows):
    """The document cell's held experts, 64 of 4096 x 4096 in one stack: a
    whole slab is 32 MiB, so a step takes 512 of its columns."""
    from ray_lightning_tpu.parallel.moe import _gmm_tiles, grouped_matmul

    assert _gmm_tiles(4096, 4096, 2) == (128, 4096, 512)
    assert _gmm_tiles(2048, 768, 2) == (128, 2048, 768)  # the latent cell's, whole
    assert _gmm_tiles(768, 2048, 2) == (128, 768, 2048)

    def call(xs, w_gate, w_up, w_down, sizes):
        mm = lambda a, w: grouped_matmul(a, w, sizes, kernel=True, interpret=False)
        h = (jax.nn.silu(mm(xs, w_gate)) * mm(xs, w_up)).astype(xs.dtype)
        return mm(h, w_down)

    stack = ((64, 4096, 4096), jnp.bfloat16)
    n = _custom_calls(
        call, ((rows, 4096), jnp.bfloat16), stack, stack, stack,
        ((64,), jnp.int32), sharding=one_chip)
    assert n == 3


@pytest.mark.parametrize("rows", [512, 16384], ids=["decode-tick", "padded-prefill"])
def test_routed_expert_matmuls_compile_at_the_cells_shapes(one_chip, rows):
    """A decode tick's 64 x 8 pairs and a padded prefill's 2048 x 8, over
    256 experts of 2048 x 768: three grouped matmuls."""
    from ray_lightning_tpu.parallel.moe import grouped_matmul

    def call(xs, w_gate, w_up, w_down, sizes):
        mm = lambda a, w: grouped_matmul(a, w, sizes, kernel=True, interpret=False)
        h = (jax.nn.silu(mm(xs, w_gate)) * mm(xs, w_up)).astype(xs.dtype)
        return mm(h, w_down)

    n = _custom_calls(
        call, ((rows, 2048), jnp.bfloat16), ((256, 2048, 768), jnp.bfloat16),
        ((256, 2048, 768), jnp.bfloat16), ((256, 768, 2048), jnp.bfloat16),
        ((256,), jnp.int32), sharding=one_chip)
    assert n == 3


@pytest.mark.parametrize("rows", [64, 512, 4096], ids=["decode-tick", "first-rung", "last-rung"])
def test_routed_expert_matmuls_compile_at_the_batch_cells_shapes(one_chip, rows):
    """Mixtral's widths, 4 layers x 8 experts in one stack: a decode tick's
    32 x 2 pairs (under the row tile: padded up to it), the first prefill
    rung's 256 x 2 and the last's 2048 x 2. A whole slab is 112 MiB, so
    ``w_gate`` / ``w_up`` go by 4096 x 512 and ``w_down``, whose whole K
    would leave 128 columns, by 1024 x 2048: three grouped matmuls."""
    from ray_lightning_tpu.parallel.moe import _gmm_tiles, grouped_matmul

    assert _gmm_tiles(4096, 14336, 2) == (128, 4096, 512)
    assert _gmm_tiles(14336, 4096, 2) == (128, 1024, 2048)

    def call(xs, w_gate, w_up, w_down, sizes):
        mm = lambda a, w: grouped_matmul(a, w, sizes, kernel=True, interpret=False)
        h = (jax.nn.silu(mm(xs, w_gate)) * mm(xs, w_up)).astype(xs.dtype)
        return mm(h, w_down)

    n = _custom_calls(
        call, ((rows, 4096), jnp.bfloat16), ((32, 4096, 14336), jnp.bfloat16),
        ((32, 4096, 14336), jnp.bfloat16), ((32, 14336, 4096), jnp.bfloat16),
        ((32,), jnp.int32), sharding=one_chip)
    assert n == 3


def test_flash_attention_with_192_wide_keys_and_128_wide_values_compiles(one_chip):
    fn = lambda q, k, v: attention(
        q, k, v, causal=True, sm_scale=192 ** -0.5, impl="flash", interpret=False)
    n = _custom_calls(
        fn, ((1, 32, 2048, 192), jnp.bfloat16), ((1, 32, 2048, 192), jnp.bfloat16),
        ((1, 32, 2048, 128), jnp.bfloat16), sharding=one_chip)
    assert n == 1


# ---------------------------------------------------------------------- #
# the KV pool is updated in place: what the chip's compiler makes of the
# engine's two programs at the serve cells' pool shapes
# ---------------------------------------------------------------------- #
_COPIES = ("copy", "copy-start", "copy-done", "dynamic-slice", "dynamic-update-slice")


def _copy_instructions(text):
    """(name, result, op) of a compiled program's instructions that copy,
    slice or stack. A fusion counts by its name, which XLA makes of what it
    fused (``copy_dynamic-update-slice_fusion``)."""
    import re

    for m in re.finditer(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(", text, re.M
    ):
        name, result, op = m.groups()
        fused = op == "fusion" and any(
            w in name.replace("_", "-") for w in ("copy", "dynamic-slice", "dynamic-update-slice"))
        if op in _COPIES or fused:
            yield name, result, op


def _pool_sized_copies(text, leaves):
    """Instructions of a compiled program that copy, slice or stack
    something of a pool leaf's size: ``leaves`` are ``(shape, dtype)`` of the
    leaves ``[L, N, *block]``; looked for are results shaped like a leaf, a
    leaf flattened over layers (and over heads, as the decode steps carry
    it), or one layer of either."""
    prefix = {"bfloat16": "bf16", "float32": "f32"}
    sized = set()
    for shape, dtype in leaves:
        (layers, pages), block = shape[:2], tuple(shape[2:])
        shapes = [shape, (layers * pages,) + block, (pages,) + block, (1, pages) + block]
        if len(block) == 3:
            shapes += [(layers * pages * block[0],) + block[1:],
                       (pages * block[0],) + block[1:]]
        sized |= {f"{prefix[jnp.dtype(dtype).name]}[{','.join(map(str, s))}]"
                  for s in shapes}
    return [f"{op} {name} {result}" for name, result, op in _copy_instructions(text)
            if any(s in result for s in sized)]


def _copies_of_at_least(text, nbytes):
    """Instructions of a compiled program that copy, slice or stack a result
    of ``nbytes`` or more."""
    import re

    width = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4}
    return [
        f"{op} {name} {result}" for name, result, op in _copy_instructions(text)
        if any(width.get(dtype, 1) * int(np.prod([int(d) for d in dims.split(",")])) >= nbytes
               for dtype, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]", result))]


def test_the_slab_check_sees_an_experts_matrix_sliced_out_of_its_stack():
    """``_copies_of_at_least`` on the instruction the batch cell's programs
    held while the experts were scanned over (the trace's
    ``dynamic-slice_bitcast_fusion.8``): seen; a matmul fusion with a result
    of the same size and a slice of a smaller one are not."""
    text = (
        "  %dynamic-slice_bitcast_fusion.8 = bf16[4096,14336]{1,0:T(8,128)(2,1)} "
        "fusion(%p.1, %p.2), kind=kLoop\n"
        "  %fusion.127 = bf16[4096,14336]{1,0:T(8,128)(2,1)} fusion(%p.3), kind=kOutput\n"
        "  ROOT %dynamic-slice.4 = bf16[4096,4096]{1,0} dynamic-slice(%p.4, %c), "
        "dynamic_slice_sizes={1,4096,4096}\n")
    found = _copies_of_at_least(text, 4096 * 14336 * 2)
    assert len(found) == 1 and "dynamic-slice_bitcast_fusion.8" in found[0]


def test_an_undonated_scatter_copies_its_leaf_and_the_check_sees_it(one_chip):
    """What ``_pool_sized_copies`` is for, at a small size: writing blocks
    into a leaf that was not donated copies the leaf first; donated, the
    scatter alone is left and the leaf is aliased."""
    leaf = ((4, 65, 8, 16, 128), jnp.bfloat16)
    shapes = (leaf, ((3,), jnp.int32), ((4, 3, 8, 16, 128), jnp.bfloat16))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    write = lambda pool, ids, blocks: pool.at[:, ids].set(blocks)
    plain = jax.jit(write).lower(*args).compile()
    assert any(c.startswith("copy ") for c in _pool_sized_copies(plain.as_text(), [leaf]))
    assert plain.memory_analysis().alias_size_in_bytes == 0
    given = jax.jit(write, donate_argnums=(0,)).lower(*args).compile()
    assert _pool_sized_copies(given.as_text(), [leaf]) == []
    assert given.memory_analysis().alias_size_in_bytes == 4 * 65 * 8 * 16 * 128 * 2


SERVE_CELLS = ["serve-dense-chat", "serve-moe-batch", "serve-mla-moe-reason"]
# the cells' engines all take prompts up to 2048 in blocks of 16
PREFILLS = [f"serve_prefill@{rung}" for rung in prefill_rungs(2048, 16)]


def _cell_engine(name, mp):
    """The paged engine of a serve cell: its configuration's widths, its
    engine settings, parameters as shapes, the kernels forced on."""
    from benchmarks import loader
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    cell = loader.Manifest().cell(name)
    settings = cell.settings["engine"]
    program = cell.family.program
    cfg = program.model_config(cell.config, max_seq=settings["max_len"], remat=False)
    params = jax.eval_shape(lambda: program.engine_params(cell.config, 1))
    mp.setenv("RLT_PAGED_KERNEL", "1")
    return InferenceEngine(params, cfg, EngineConfig(**settings))


def _cell_specs(engine, one_chip):
    """(program, fn, argument shapes on the described chip) of an engine's
    programs, prefill under ``serve_prefill@<rung>``."""
    for name, fn, args in engine._program_specs():
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args)
        if name == "serve_prefill":
            name = f"{name}@{args[2].shape[1]}"
        yield name, fn, shapes


def _compile_cell(name, topo, one_chip):
    """A serve cell's programs compiled for the chip, prefill once a rung:
    ({program: compiled}, the pool's leaves, the pool's bytes). The kernels
    are forced on, as on the chip, and asked to compile rather than
    interpret."""
    mp = pytest.MonkeyPatch()
    try:
        engine = _cell_engine(name, mp)
        leaves = [(a.shape, a.dtype) for a in engine.pool.cache.values()]
        pool_bytes = sum(int(a.nbytes) for a in engine.pool.cache.values())
        mp.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
        compiled = {program: fn.lower(*shapes).compile()
                    for program, fn, shapes in _cell_specs(engine, one_chip)}
    finally:
        mp.undo()
    return compiled, leaves, pool_bytes


@pytest.fixture(scope="module", params=SERVE_CELLS)
def cell_programs(request, topo, one_chip):
    """The paged engine of a serve cell (its configuration's widths, its
    engine settings, parameters as shapes), with its programs compiled for
    the chip, prefill once a rung: {program: compiled} under the names
    ``serve_decode`` and ``serve_prefill@<rung>``, and the pool's leaves."""
    compiled, leaves, pool_bytes = _compile_cell(request.param, topo, one_chip)
    assert sorted(compiled) == sorted(["serve_decode", *PREFILLS])
    return request.param, compiled, leaves, pool_bytes


@pytest.mark.parametrize("program", ["serve_decode", *PREFILLS])
def test_serve_programs_update_the_pool_in_place_at_the_cells_shapes(
    cell_programs, program
):
    """The chat cell's K/V pool (2 x [16, 2401, 8, 16, 128] bf16, 2.5 GB),
    the batch cell's (2 x [4, 1281, ...], 0.34 GB) and the reason cell's
    latent pool ([1 | 4, 8193, 16, 640], 0.84 GB): each program, prefill at
    every rung, aliases the whole pool, nothing of a leaf's or a layer's
    size is copied, sliced or stacked, and the temporaries stay a small part
    of the pool. Written ``.at[phys, :, off, :]`` on the five-axis pool, the
    decode step fails all three: the carried pool takes the scatter's layout
    and is copied whole to the kernel's, every layer. Prefill's temporaries
    are the prompt's activations, which do not grow with the pool; they are
    held under the largest leaf (a copy of one would be at least that),
    where a leaf is the larger: not in the batch cell, whose leaf is
    0.17 GB beside the 14336-wide activations of 2 x 2048 routed pairs.
    There what is held out of the temporaries is an expert's matrix (4096 x
    14336 bf16, 117 MB; scanned over, 2.8 GB of them were in every
    program): nothing of that size or more is copied, sliced or stacked."""
    cell, compiled, leaves, pool_bytes = cell_programs
    exe = compiled[program]
    assert _pool_sized_copies(exe.as_text(), leaves) == []
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    if cell == "serve-moe-batch":
        assert _copies_of_at_least(exe.as_text(), 4096 * 14336 * 2) == []
    if program == "serve_decode":
        assert mem.temp_size_in_bytes < pool_bytes / 10
    elif cell != "serve-moe-batch":
        largest = max(int(np.prod(s)) * jnp.dtype(d).itemsize for s, d in leaves)
        assert mem.temp_size_in_bytes < largest


@pytest.mark.parametrize("program", PREFILLS)
def test_every_rung_of_prefill_is_the_labelled_module_with_its_kernels_inside(
    cell_programs, program
):
    """Whatever the rung, the program is the module ``jit_serve_prefill`` (one
    name in a device trace's ``XLA Modules``) and holds the kernels prefill
    holds today: flash attention, and in the cells with experts the grouped
    matmul of the routed pairs."""
    cell, compiled, _, _ = cell_programs
    text = compiled[program].as_text()
    assert text.startswith("HloModule jit_serve_prefill")
    kernels = set(_kernel_instructions(text))
    assert "flash_fwd" in kernels
    assert ("gmm" in kernels) == (cell != "serve-dense-chat")


def test_the_llama_cells_first_rung_is_one_program_for_the_prompt_and_the_rows(
    cell_programs
):
    """PR 43: in the chat and batch cells ``serve_prefill@256`` is the tick
    that admits a prompt, whole: the prompt's flash attention AND the decode
    rows' paged attention and sampler in one module, its arguments the
    decode program's behind the prompt row and its write table, the pool
    still aliased whole with nothing of a leaf's or a layer's size copied
    (the parametrised test above holds every rung to that; this one says
    what the program is). The longer rungs' programs are the prompt's
    alone, and so is every rung of the reason cell, whose family provides
    no such step."""
    cell, compiled, leaves, pool_bytes = cell_programs
    exe = compiled["serve_prefill@256"]
    kernels = set(_kernel_instructions(exe.as_text()))
    fused = cell != "serve-mla-moe-reason"
    assert "flash_fwd" in kernels
    assert ({"paged_decode_attention", "fused_argmax"} <= kernels) == fused
    if fused:  # the prompt row and its write table ahead of decode's own
        assert len(jax.tree_util.tree_leaves(exe.input_shardings[0])) == len(
            jax.tree_util.tree_leaves(compiled["serve_decode"].input_shardings[0])) + 2
    assert _pool_sized_copies(exe.as_text(), leaves) == []
    assert exe.memory_analysis().alias_size_in_bytes >= pool_bytes
    assert "paged_decode_attention" not in _kernel_instructions(
        compiled["serve_prefill@512"].as_text())


def test_the_decode_program_holds_the_grouped_matmul_where_there_are_experts(
    cell_programs
):
    """``jit_serve_decode`` of the cells with experts computes the routed
    pairs by the kernel (the batch cell's 64 pairs are under its row tile
    and are padded up to it: ``lax.ragged_dot`` is for where Pallas is not
    native); the chat cell's has none."""
    cell, compiled, _, _ = cell_programs
    text = compiled["serve_decode"].as_text()
    assert text.startswith("HloModule jit_serve_decode")
    assert ("gmm" in _kernel_instructions(text)) == (cell != "serve-dense-chat")
    assert ("ragged-dot" in text) is False


def test_prefill_temporaries_shrink_with_the_rung(cell_programs):
    """What a shorter rung saves besides time: the prompt's activations. Each
    rung's temporaries are under those of the next, and the first rung's
    under a third of the last's, in the batch cell too since no expert's
    matrices are among them."""
    cell, compiled, _, _ = cell_programs
    temps = [compiled[p].memory_analysis().temp_size_in_bytes for p in PREFILLS]
    assert temps == sorted(temps) and len(set(temps)) == len(temps)
    assert temps[0] < temps[-1] / 3


# ---------------------------------------------------------------------- #
# the sparse / linear attention cell (benchmarks/configs/minicpm-sala-d4.json,
# workloads/serve-sparse-linear-long.json): a pool with a state kind
# ---------------------------------------------------------------------- #
LONG_CELL = "serve-sparse-linear-long"
LONG_PREFILLS = [f"serve_prefill@{rung}" for rung in prefill_rungs(16384, 64)]
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def long_cell_programs(topo, one_chip):
    compiled, leaves, pool_bytes = _compile_cell(LONG_CELL, topo, one_chip)
    assert sorted(compiled) == sorted(["serve_decode", *LONG_PREFILLS])
    return compiled, leaves, pool_bytes


@pytest.mark.parametrize("program", ["serve_decode", *LONG_PREFILLS])
def test_the_sparse_linear_cells_programs_compile_and_fit_the_chip(long_cell_programs, program):
    """32 slots, ``max_len`` 20,480, pages of 64: K and V 2 x [1, 10241, 2,
    64, 128] bf16, the pooled keys [1, 10241, 2, 4, 128], the state [3, 32,
    32, 128, 128] float32, 0.89 GB together. Every program (the rungs 256 to
    16,384 and the decode step) aliases the whole pool, the state among it,
    copies nothing of a leaf's size, and fits the chip's 16 GB with the
    weights and its own temporaries: under 2 GB of them at the longest rung,
    under a tenth of the pool in the decode step."""
    compiled, leaves, pool_bytes = long_cell_programs
    assert 0.88e9 < pool_bytes < 0.90e9
    exe = compiled[program]
    found = _pool_sized_copies(exe.as_text(), leaves)
    if program == "serve_decode":
        # the compiler stages the pooled keys' leaf (21 MB, gathered whole by
        # every row's scores) through its fast memory: a copy there and back
        # a tick, of no leaf that a kernel reads
        pooled = ("bf16[1,10241,2,4,128]", "bf16[20482,4,128]")  # the leaf, and flattened
        found = [f for f in found if not (
            f.startswith(("copy-start ", "copy-done ")) and any(p in f for p in pooled))]
    else:
        # prefill writes the slot's state whole where it is: an update in
        # place of the donated leaf, which the alias below holds it to
        found = [f for f in found if not (
            f.startswith("dynamic-update-slice ") and "f32[3,32,32,128,128]" in f)]
    assert found == []
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < HBM_BYTES / 2
    if program == "serve_decode":
        assert mem.temp_size_in_bytes < pool_bytes / 10
    else:
        assert mem.temp_size_in_bytes < 2.0e9


def test_the_sparse_linear_cells_programs_hold_their_kernels_by_name(long_cell_programs):
    """The decode step reads the chosen blocks through ``paged_decode_attention``
    and moves the states on by ``lightning_decode``; prefill scans by
    ``lightning_prefill`` at every rung and attends by ``flash_fwd`` under
    ``dense_len`` (8,192) and by ``flash_fwd_selected`` from there on."""
    compiled, _, _ = long_cell_programs
    decode = compiled["serve_decode"].as_text()
    assert decode.startswith("HloModule jit_serve_decode")
    assert set(_kernel_instructions(decode)) == {
        "paged_decode_attention", "lightning_decode", "rmsnorm", "fused_argmax"}
    assert _kernel_instructions(decode).count("lightning_decode") == 3
    for program in LONG_PREFILLS:
        text = compiled[program].as_text()
        assert text.startswith("HloModule jit_serve_prefill")
        rung = int(program.split("@")[1])
        attends = "flash_fwd_selected" if rung >= 8192 else "flash_fwd"
        assert set(_kernel_instructions(text)) == {attends, "lightning_prefill", "rmsnorm"}
    temps = [compiled[p].memory_analysis().temp_size_in_bytes for p in LONG_PREFILLS]
    assert temps == sorted(temps)


# ---------------------------------------------------------------------- #
# the state-space cell (benchmarks/configs/jamba2-3b.json,
# workloads/serve-ssm-chat.json): two state leaves beside K and V, 256 slots
# ---------------------------------------------------------------------- #
SSM_CELL = "serve-ssm-chat"
SSM_PREFILLS = [f"serve_prefill@{rung}" for rung in prefill_rungs(2048, 64)]
SCAN_STATES_AT_2048 = 2048 * 5120 * 16 * 4  # one [L, C, N] float32 array: 671 MB


@pytest.fixture(scope="module")
def ssm_cell_programs(topo, one_chip):
    compiled, leaves, pool_bytes = _compile_cell(SSM_CELL, topo, one_chip)
    assert sorted(compiled) == sorted(["serve_decode", *SSM_PREFILLS])
    return compiled, leaves, pool_bytes


@pytest.mark.parametrize("program", ["serve_decode", *SSM_PREFILLS])
def test_the_state_space_cells_programs_compile_and_fit_the_chip(ssm_cell_programs, program):
    """256 slots, ``max_len`` 3,072, pages of 64: K and V 2 x [2, 12289, 1,
    64, 128] bf16 (0.81 GB), the scan state [26, 256, 16, 5120] float32 (2.18
    GB) and the convolution's tail [26, 256, 3 x 5120] bf16 (0.20 GB): 3.19 GB
    beside 6.06 GB of weights. Every program (the rungs 256 to 2,048 and the
    decode step) aliases the whole pool, both state leaves among it, copies
    nothing of a leaf's size, and fits the chip's 16 GB with the weights and
    its own temporaries. No prefill holds as much as ONE ``[L, 5120, 16]``
    array of its rung's scan states (671 MB at 2,048): the scan's state
    stays in the kernel."""
    compiled, leaves, pool_bytes = ssm_cell_programs
    assert 3.18e9 < pool_bytes < 3.21e9
    exe = compiled[program]
    found = _pool_sized_copies(exe.as_text(), leaves)
    # prefill writes the slot's state and tail whole where they are, and the
    # decode step one layer's tails: an update in place of the donated leaf,
    # which the alias below holds it to
    found = [f for f in found if not (
        f.startswith("dynamic-update-slice ")
        and ("f32[26,256,16,5120]" in f or "bf16[26,256,15360]" in f))]
    if program == "serve_decode":
        # the shift itself: one layer's tails (7.9 MB of the leaf's 204) read,
        # shifted by the rows' inputs and written back where they were
        found = [f for f in found if not any(
            one in f for one in ("bf16[1,256,15360]", "bf16[256,15360]"))]
    assert found == []
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < HBM_BYTES * 0.75
    if program == "serve_decode":
        assert mem.temp_size_in_bytes < pool_bytes / 10
    else:
        rung = int(program.split("@")[1])
        assert mem.temp_size_in_bytes < SCAN_STATES_AT_2048 * rung / 2048


def test_the_state_space_cells_programs_hold_their_kernels_by_name(ssm_cell_programs):
    """The decode step moves the scan states on by ``mamba_decode``, once in
    each of the three scanned runs of Mamba layers, and attends by
    ``paged_decode_attention``, once an attention layer; prefill scans by
    ``mamba_scan`` and attends by ``flash_fwd`` at every rung."""
    compiled, _, _ = ssm_cell_programs
    decode = compiled["serve_decode"].as_text()
    assert decode.startswith("HloModule jit_serve_decode")
    kernels = _kernel_instructions(decode)
    assert set(kernels) == {"paged_decode_attention", "mamba_decode", "rmsnorm", "fused_argmax"}
    assert kernels.count("mamba_decode") == 3 and kernels.count("paged_decode_attention") == 2
    for program in SSM_PREFILLS:
        text = compiled[program].as_text()
        assert text.startswith("HloModule jit_serve_prefill")
        kernels = _kernel_instructions(text)
        assert set(kernels) == {"flash_fwd", "mamba_scan", "rmsnorm"}
        assert kernels.count("mamba_scan") == 3 and kernels.count("flash_fwd") == 2
    temps = [compiled[p].memory_analysis().temp_size_in_bytes for p in SSM_PREFILLS]
    assert temps == sorted(temps)


def test_mamba_decode_aliases_the_stack_of_states_at_the_cells_shapes(one_chip):
    """The kernel alone at 256 rows of 5,120 channels over the stack of 26
    layers' states: the stack goes in and comes out as one buffer (2.18 GB
    aliased, no temporary of its size), the layer a traced scalar."""
    from ray_lightning_tpu.ops.selective_scan import mamba_decode

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    exe = jax.jit(
        lambda x, dt, b, c, a, d, states, layer: mamba_decode(
            x, dt, b, c, a, d, states, layer, kernel=True, interpret=False),
        donate_argnums=(6,),
    ).lower(f32(256, 5120), f32(256, 5120), f32(256, 16), f32(256, 16), f32(16, 5120),
            f32(5120), f32(26, 256, 16, 5120),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    mem = exe.memory_analysis()
    stack = 26 * 256 * 16 * 5120 * 4
    assert mem.alias_size_in_bytes >= stack and mem.temp_size_in_bytes < stack / 100
    assert _kernel_instructions(exe.as_text()) == ["mamba_decode"]


@pytest.mark.parametrize("rung", [256, 2048])
def test_mamba_scan_compiles_at_the_cells_rungs(one_chip, rung):
    from ray_lightning_tpu.ops.selective_scan import mamba_scan

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    exe = jax.jit(
        lambda x, dt, b, c, a, d, n: mamba_scan(
            x, dt, b, c, a, d, n, kernel=True, interpret=False),
    ).lower(f32(rung, 5120), f32(rung, 5120), f32(rung, 16), f32(rung, 16), f32(16, 5120),
            f32(5120), jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert _kernel_instructions(exe.as_text()) == ["mamba_scan"]


# ---------------------------------------------------------------------- #
# the accepted serve cells' programs are the parent's
# ---------------------------------------------------------------------- #
def _fingerprint(fn, shapes):
    """sha256 of a serving program as it is handed to the compiler: the
    lowered text, in which a Mosaic kernel is an opaque body that carries the
    source's paths and line numbers (cut out), and the program's jaxpr, which
    holds every kernel's body as equations and no line numbers (object
    addresses cut out). A change to an accepted cell's program moves one of
    the two; a moved line of the engine moves neither."""
    import hashlib
    import re

    text = re.sub(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+', r"\1", fn.lower(*shapes).as_text())
    jaxpr = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(getattr(fn, "_fn", fn))(*shapes)))
    return hashlib.sha256((text + jaxpr).encode()).hexdigest()


@pytest.mark.parametrize(
    "cell", [*SERVE_CELLS, "serve-swa-moe-doc"])
def test_the_accepted_serve_cells_programs_lower_to_the_recorded_text(
    cell, topo, one_chip, monkeypatch
):
    """``tests/serve_programs_lowered.json`` holds the fingerprint of every
    serving program of the four accepted serve cells as the parent of PR 34
    lowered them for a described v5e (PR 33's method: a program that lowers
    to the same text is the same program, so the cell cannot have moved). A
    PR that changes one of these programs on purpose records the file anew
    (``_fingerprint`` over ``_cell_specs``) and says which and why. PR 35
    recorded the four ``serve_decode`` entries anew: the decode program takes
    the output of the decode program before it and a row's token from there
    where the host's is -1 (one ``select`` ahead of the model's step); every
    ``serve_prefill@<rung>`` entry stood as it was. PR 40 recorded the eleven
    ``serve_prefill@<rung>`` entries of 1,024 positions and more anew:
    ``flash_fwd`` inside them walks ``flash_schedule``'s pairs (a grid of
    three axes and three scalar-prefetch operands in place of four axes); the
    eight entries at 256 and 512 (one tile a sequence, nothing to skip, the
    rectangular grid) and the four ``serve_decode`` entries stand. PR 42
    recorded the same eleven ``serve_prefill@<rung>`` entries of 1,024
    positions and more anew: ``flash_fwd`` inside them walks the tile
    ``_fwd_tile`` reads off the rung (1,024 x 1,024 where it walked 512 x 512:
    one tile at the 1,024 rung, on the rectangular grid; three pairs at
    2,048); the eight entries at 256 and 512 (one tile, as before) and the
    four ``serve_decode`` entries stand to the letter. PR 43 recorded
    ``serve_prefill@256`` of ``serve-dense-chat`` and ``serve-moe-batch``
    anew: in an engine of the Llama family the first rung's program is the
    tick that admits a prompt, whole (the prompt's positions in front of the
    decode rows through one pass of the layers, the decode program's
    arguments behind the prompt's); their three longer rungs, all four
    ``serve_decode`` entries and every entry of the reason and doc cells
    stand to the letter. PR 46 recorded none: the routed path's backward
    (``grouped_matmul``'s ``custom_vjp``, the sort's gathers that go back as
    gathers) sits behind ``differentiable=True``, which the model that trains
    sets and no serving family does, so all 23 entries stand."""
    import json
    import os

    recorded = json.load(open(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "serve_programs_lowered.json")))
    engine = _cell_engine(cell, monkeypatch)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    got = {f"{cell}/{program}": _fingerprint(fn, shapes)
           for program, fn, shapes in _cell_specs(engine, one_chip)}
    want = {k: v for k, v in recorded.items() if k.startswith(cell + "/")}
    assert sorted(got) == sorted(want) and len(want) >= 5
    assert [k for k in want if got[k] != want[k]] == []


# ---------------------------------------------------------------------- #
# the expert model's train step: the grouped products under jax.grad
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def moe_train_step(topo, one_chip):
    """``train-moe-8k``'s step as ``Trainer`` builds it, less the trainer:
    the family's module at the cell's widths, the loss and its gradient, the
    module's own AdamW; parameters and optimizer state donated, everything
    as shapes on the described chip."""
    import optax

    from benchmarks import loader
    from ray_lightning_tpu.models import lfm2

    cell = loader.Manifest().cell("train-moe-8k")
    sizes, job, program = cell.config, cell.traffic, cell.family.program
    cfg = program.model_config(sizes, max_seq=job["seq_len"], **cell.settings["model"])
    tx = program.make_module(cfg, sizes, 1, job["optimizer"]).configure_optimizers()
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: cell.family.weights.make_params(sizes, cell.family.weights.seed_keys(sizes, 1))))
    state = on_chip(jax.eval_shape(tx.init, params))
    tokens = jax.ShapeDtypeStruct(
        (job["rows_per_chip"], job["seq_len"]), jnp.int32, sharding=one_chip)

    def step(params, state, tokens):
        (loss, logs), grads = jax.value_and_grad(
            lambda p: lfm2.lm_loss(p, tokens, cfg), has_aux=True)(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss, logs["moe_sizes"]

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
        exe = jax.jit(step, donate_argnums=(0, 1)).lower(params, state, tokens).compile()
    finally:
        mp.undo()
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    return exe, held


def test_the_expert_train_step_fits_the_chip_with_its_state_in_place(moe_train_step):
    """Weights and two moments of 893.7 M parameters go in and come out as
    the same buffers (6 bytes each); beside them the gradients and a layer's
    activations under remat: under 16 GB, and over the quarter of the chip a
    cell has to fill."""
    exe, held = moe_train_step
    mem = exe.memory_analysis()
    assert held == 893_696_256
    assert mem.argument_size_in_bytes >= 6 * held and mem.alias_size_in_bytes >= 6 * held
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0.25 * 16e9 < total < 11e9  # 9.73 GB when this was written


def test_the_expert_train_step_differentiates_through_the_grouped_kernels(moe_train_step):
    """Four expert layers x three stacks: ``gmm`` forward, again under remat
    and once more for the rows' gradient, ``tgmm`` for the stacks'; the names
    the benchmark finds them by; no ``ragged_dot`` and no dispatch array of
    ``[tokens, experts, capacity]``; the attention layer's three flash
    kernels beside them."""
    from collections import Counter

    text = moe_train_step[0].as_text()
    kernels = Counter(_kernel_instructions(text))
    assert kernels["gmm"] == 4 * 3 * 3 and kernels["tgmm"] == 4 * 3
    assert kernels["flash_fwd"] == 2 and kernels["flash_bwd_dq"] == kernels["flash_bwd_dkv"] == 1
    assert "ragged" not in text
    assert "[16384,32," not in text and "[16384,16," not in text


@pytest.mark.parametrize("k,n", [(2048, 1792), (1792, 2048)], ids=["gate-up", "down"])
def test_the_grouped_kernels_gradients_compile_at_the_cells_shapes(one_chip, k, n):
    """One stack of 16 held experts under 65,536 sorted pairs: the product,
    the rows' gradient (the same kernel over the stack transposed) and the
    stack's (``tgmm``, its float32 accumulator a ``_gmm_tiles`` tile at four
    bytes an element)."""
    from ray_lightning_tpu.parallel.moe import grouped_matmul

    def grads(xs, w, sizes):
        return jax.grad(
            lambda xs, w: grouped_matmul(
                xs, w, sizes, kernel=True, interpret=False, differentiable=True).sum(),
            argnums=(0, 1))(xs, w)

    text = _compiled_text(
        grads, ((65536, k), jnp.bfloat16), ((16, k, n), jnp.bfloat16), ((16,), jnp.int32),
        sharding=one_chip)
    # the unused forward is dropped; straight under the outer jit the compiler
    # names a call after ``jit(gmm)`` whole, inside a step's scopes after ``gmm``
    assert [k.removeprefix("jit_") for k in _kernel_instructions(text)] == ["gmm", "tgmm"]
