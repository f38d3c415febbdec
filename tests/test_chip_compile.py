"""Compile the main path's Pallas kernels for the chip, without the chip.

The TPU compiler is installed here and compiles for a described
``v5e:2x2`` (section 2 of the ``on-chip-measurement`` guide): what it
refuses — a block spec off the (8, 128) tiling, too much VMEM, a
relayout Mosaic lacks — costs no chip time. Interpret mode shows none
of that, so each case calls the inner kernel function with
``interpret=False`` (the dispatchers would pick interpret mode or the
XLA path under the tests' CPU backend) and looks for the kernel
(``tpu_custom_call``) in the compiled program.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load the TPU's library, and every
xdist worker imports every test file. Keep these compiles in this one
file for the same reason.
"""

import contextlib
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` -> an abstract array placed on one v5e chip.
    The persistent compile cache is off around these compiles: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.as_text()


def _kernel_instruction_names(hlo_text):
    """Names of the instructions that are Mosaic kernels: what a device
    trace shows for them (``%flash_attention_fwd.1`` -> the stem)."""
    import re

    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_][\w-]*?)(?:\.\d+)? = [^\n]*custom_call_target="
        r'"tpu_custom_call"', hlo_text)}


# -- flash attention: b=1 s=2048 n=32 d=128 (Llama-2-7B, the smoke's), and
# mistral-7b.train-tp4's own call: 2 sequences x 8 local heads, S = 4096

_FLASH_SHAPES = {"smoke": (1, 2048, 32, 128), "train_tp4": (2, 4096, 8, 128)}


def _flash_args(chip, shape="smoke"):
    qkv = chip(_FLASH_SHAPES[shape], jnp.bfloat16)
    return qkv, qkv, qkv, chip((1,), jnp.uint32)


def _flash(dropout_p, causal=True):
    """The kernel under the blocks ``flash_attention`` derives for these
    sequences (its defaults: 512 divides both)."""
    from neuronx_distributed_tpu.ops.flash_attention import _flash_pallas

    def fwd(q, k, v, seed):
        return _flash_pallas(q, k, v, seed, causal, 512, 512,
                             1.0 / math.sqrt(128), False, dropout_p)
    return fwd


def _flash_loss(causal=True):
    fwd = _flash(0.0, causal)

    def loss(q, k, v, seed):
        return jnp.sum(fwd(q, k, v, seed).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


_FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                  "flash_attention_bwd_dkv")


def _assert_flash_kernels(text, want):
    got = _kernel_instruction_names(text)
    assert len(got) == len(want), got
    for name in want:
        assert sum(name in g for g in got) == 1, (name, got)


@pytest.mark.parametrize("dropout_p", [0.0, 0.1], ids=["plain", "dropout"])
def test_flash_forward(chip, dropout_p):
    _assert_kernel_compiles(_flash(dropout_p), *_flash_args(chip))


def test_flash_forward_backward(chip):
    _assert_kernel_compiles(_flash_loss(), *_flash_args(chip))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_forward_at_the_train_cell(chip, causal):
    text = _assert_kernel_compiles(_flash(0.0, causal),
                                   *_flash_args(chip, "train_tp4"))
    _assert_flash_kernels(text, _FLASH_KERNELS[:1])


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_forward_backward_at_the_train_cell(chip, causal):
    text = _assert_kernel_compiles(_flash_loss(causal),
                                   *_flash_args(chip, "train_tp4"))
    _assert_flash_kernels(text, _FLASH_KERNELS)


def test_kernel_names_are_the_device_instruction_names(chip):
    """``pl.pallas_call(name=)`` names the custom call's instruction,
    which is the name of its events in a device trace: the benchmark's
    ``device_op_share`` readers search it. A transformation applied with
    no ``jit`` between wraps the name (``jvp_flash_attention_fwd_``
    here; through the jitted ``flash_attention`` the name stands alone).
    Without a name the call takes its enclosing scope's (``shard_map``,
    ``jvp_jit_flash_attention__``)."""
    text = _assert_kernel_compiles(_flash_loss(), *_flash_args(chip))
    _assert_flash_kernels(text, _FLASH_KERNELS)


# -- paged attention: the packed serving step's shapes ----------------------

# (tokens, kv heads, table columns, pool blocks, window): the smoke test's
# widths at two packed widths, the benchmark cells mixtral-8x7b.serve-batch
# (and mistral-7b's: tiles of 32 rows) and evabyte.serve-docs (one tile of
# 128 rows, the two masks), and a disaggregated prefill worker's width
# that is no whole number of tiles
_PAGED_SHAPES = {"T32": (32, 32, 16, 64, None), "T256": (256, 32, 16, 64, None),
                 "serve_batch": (128, 8, 20, 320, None),
                 "serve_docs": (128, 32, 40, 176, (2048, 17)),
                 "T100": (100, 8, 20, 320, None)}


def _mosaic_ops(hlo_text):
    """Names of the operations in the bodies of the Mosaic kernels of a
    compiled program (the custom call carries its MLIR as bytecode, whose
    string section holds them)."""
    import base64
    import re

    bodies = [base64.b64decode(b) for b in re.findall(
        r'"custom_call_config":\{"body":"([^"]+)"', hlo_text)]
    assert bodies
    return {op.decode() for body in bodies
            for op in re.findall(rb"tpu\.[a-z_]+", body)}


@pytest.mark.parametrize("quantized,shape", [
    pytest.param(quantized, shape, id=f"{shape}-{'int8' if quantized else 'fp'}")
    for quantized in (False, True) for shape, geometry in _PAGED_SHAPES.items()
    if not (quantized and geometry[-1])])   # a window-summary pool is float
def test_paged_attention(chip, quantized, shape):
    from neuronx_distributed_tpu.ops.paged_attention import (
        _paged_attention_pallas, run_blocks)

    tokens, kv, cols, nb, window = _PAGED_SHAPES[shape]
    n, d, bs, layers = 32, 128, 128, 2
    pool = chip((layers, nb, bs, kv, d),
                jnp.int8 if quantized else jnp.bfloat16)
    scale = chip((layers, nb, bs, kv), jnp.float32) if quantized else None
    # a narrow group's blocks ride in runs where two ring halves of them
    # fit (``unit_blocks``): 8 K/V heads of 128 in bf16 are 512 KiB a
    # block and ride in fours, 32 heads are 2 MiB and ride in none, which
    # is the kernel a pair a turn
    assert run_blocks(pool, pool, n // kv, cols) == {
        (8, False): 4, (8, True): 8, (32, False): 1, (32, True): 2}[
            kv, quantized]
    fn = functools.partial(_paged_attention_pallas,
                           scale=1.0 / math.sqrt(d), interpret=False,
                           window=window)
    text = _assert_kernel_compiles(
        fn, chip((tokens, n, d), jnp.bfloat16), pool, pool,
        chip((nb, bs), jnp.int32), chip((tokens, cols), jnp.int32),
        chip((tokens,), jnp.int32), chip((), jnp.int32), scale, scale)
    # the benchmark's readers and its `correct` find the kernel by name
    assert _kernel_instruction_names(text) == {
        "eva_attention" if window else "paged_attention"}
    # the products are the MXU's, the blocks the kernel's own copies, a
    # head's rows a strided read of the block as the pool lays it
    assert {"tpu.matmul", "tpu.enqueue_dma", "tpu.strided_load"} <= (
        _mosaic_ops(text))


def test_a_pool_of_large_blocks_takes_the_kernel_it_took():
    """EvaByte's pool (32 heads of 128: 2 MiB a block) rides in no runs,
    and its kernel is the kernel as PR 45's tree traced it at the cell's
    shapes: the jaxpr of the call, kernel body and all (a jaxpr carries no
    source locations; the Mosaic bytecode does), by its sha256. A PR that
    changes the pair-a-turn kernel on purpose records its new hash here."""
    import hashlib

    from neuronx_distributed_tpu.ops.paged_attention import (
        _paged_attention_pallas, run_blocks)

    tokens, kv, cols, nb, window = _PAGED_SHAPES["serve_docs"]
    n, d, bs, layers = 32, 128, 128, 2
    shape = jax.ShapeDtypeStruct
    pool = shape((layers, nb, bs, kv, d), jnp.bfloat16)
    assert run_blocks(pool, pool, n // kv, cols) == 1
    text = str(jax.make_jaxpr(functools.partial(
        _paged_attention_pallas, k_scale=None, v_scale=None,
        scale=1.0 / math.sqrt(d), interpret=False, window=window))(
            shape((tokens, n, d), jnp.bfloat16), pool, pool,
            shape((nb, bs), jnp.int32), shape((tokens, cols), jnp.int32),
            shape((tokens,), jnp.int32), shape((), jnp.int32)))
    assert "eva_attention" in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "7b4aa3f7535ae086398a0265ef65dcd560ac0c9810ccce1e4a5768bdf9570879")


# -- the paged forward: the pool rides the layer scan as its carry -----------
# Scanned in and out, every layer's pool was sliced out of one stack
# (dynamic-slice), written into another (dynamic-update-slice), and the
# donated argument copied whole because an output built beside it cannot
# alias it: three pool-sized copies a step (PERF.md, PR 30). As the carry,
# addressed at (layer, block) by the scatters and by the kernel's index
# map, the donated stacks are written in place. These cases hold every
# later form of the paged body to that, at the serving cells' pool shapes.

# family, K/V heads, blocks, slots, table columns, int8 pool
_POOLS = {"full": ("llama", 8, 320, 32, 20, False),
          "window_summary": ("evabyte", 32, 176, 8, 40, False),
          "int8": ("llama", 8, 320, 32, 20, True)}


def _in_place_writes(hlo_text, large):
    """Of ``large`` (:func:`_top_level_results`), the fusions that end in a
    ``scatter``: the device writes those into their operand's buffer."""
    import re

    root, comp = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
        m = re.match(r"^\s*ROOT %[\w.\-]+ = \(?[a-z0-9]+\[.*? ([a-z\-]+)\(",
                     line)
        if m:
            root[comp] = m.group(1)
    calls = dict(re.findall(
        r"%([\w.\-]+) = [^\n]* fusion\([^\n]*calls=%([\w.\-]+)", hlo_text))
    return [r for r in large
            if r[0] == "fusion" and root.get(calls.get(r[1])) == "scatter"]


@pytest.fixture
def on_one_chip(topo, monkeypatch):
    """The mesh on a described chip and the paged dispatcher steered to
    the compiled kernel, as the chip's own backend would steer it."""
    from neuronx_distributed_tpu.ops import paged_attention as pa
    from neuronx_distributed_tpu.parallel import mesh as ps

    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    pa.paged_attention_impl.cache_clear()
    ps.destroy_model_parallel()
    ps.initialize_model_parallel(devices=[topo.devices[0]])
    yield
    ps.destroy_model_parallel()
    pa.paged_attention_impl.cache_clear()


@pytest.mark.parametrize("pool", list(_POOLS))
def test_paged_forward_writes_the_donated_pool_in_place(chip, on_one_chip,
                                                        pool):
    import re

    from flax.core import meta

    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.models import evabyte, llama

    family, kv, nb, slots, cols, quantized = _POOLS[pool]
    layers, heads, d, bs, tokens = 2, 32, 128, 128, 128
    widths = dict(hidden_size=heads * d, intermediate_size=1024,
                  num_layers=layers, num_heads=heads, num_kv_heads=kv,
                  vocab_size=512, max_seq_len=32768, dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16)
    if family == "evabyte":
        cfg = evabyte.EvaByteConfig(**widths)
        model = evabyte.EvaByteForCausalLM(cfg)
    else:
        cfg = llama.LlamaConfig(**widths)
        model = llama.LlamaForCausalLM(cfg)
    forward = cfg.serving_family().forward
    abstract = functools.partial(
        jax.tree_util.tree_map, lambda x: chip(x.shape, x.dtype))
    params = abstract(meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    if quantized:
        init = paging.init_quantized_paged_kv_cache
    else:
        init = functools.partial(paging.init_paged_kv_cache,
                                 dtype=jnp.bfloat16)
    cache = abstract(jax.eval_shape(
        lambda: init(layers, nb, bs, kv, d, slots, cols)))

    def step(params, cache, tokens, positions, slot_ids):
        return forward(cfg, params, tokens, positions, cache,
                       slot_ids=slot_ids)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, chip((1, tokens), jnp.int32),
        chip((1, tokens), jnp.int32), chip((tokens,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {
        "eva_attention" if family == "evabyte" else "paged_attention"}

    # one layer's K (or V) pool: nothing that large is computed or copied;
    # what is left writes rows into the stacks where they lie
    layer_pool = nb * bs * kv * d
    large = _top_level_results(text, layer_pool)
    writes = _in_place_writes(text, large)
    assert [r for r in large if r not in writes] == []
    assert len(writes) == (4 if family == "evabyte" else 2), writes
    assert (compiled.memory_analysis().temp_size_in_bytes
            < layer_pool * cache.k.dtype.itemsize / 4)

    # every stack is handed back in the buffer it came in
    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape.startswith(f"{layers},{nb},{bs},{kv}")]
    assert len(stacks) == (4 if quantized else 2)
    assert set(stacks) <= aliased, (stacks, header)


# -- the sparse-state cache (MiniCPM-SALA): the selection is the walk ----------
# The cell minicpm-sala.serve-longdocs: 128 rows, 32 query heads in 2 groups
# over 2 K/V heads of 128, 264 table columns, 4,224 blocks of 128.

@pytest.mark.parametrize("tokens,rows", [(128, 128), (100, 112)])
def test_sparse_paged_attention(chip, tokens, rows):
    """The tile kernel at the cell's shapes: one tile of the packed rows
    (whole parts of 16: a prefill worker's 100 rows ride as 112), a
    group's 16 heads stacked; under the one name, the Mosaic body copies
    its blocks itself and multiplies on the MXU, and the walk beside it
    is built without a sort."""
    from neuronx_distributed_tpu.ops import sparse_attention as sp

    groups, rep, d, bs, cols, nb, layers = 2, 16, 128, 128, 264, 4224, 4
    spec = sp.SparseSpec()
    assert spec.walk_width(bs, cols) == 64
    assert sp.narrow_height(jnp.bfloat16) == 16
    assert sp.tile_height(tokens, rep, d, jnp.bfloat16) == rows
    pool = chip((layers, nb, groups, bs, d), jnp.bfloat16)
    fn = functools.partial(sp._sparse_paged_pallas, spec=spec,
                           scale=1.0 / math.sqrt(d), interpret=False)
    text = _assert_kernel_compiles(
        lambda *a: fn(*a), chip((tokens, groups, rep, d), jnp.bfloat16),
        pool, pool, chip((), jnp.int32), chip((tokens, cols), jnp.int32),
        chip((tokens,), jnp.int32), chip((tokens, groups, cols), jnp.int32))
    assert _kernel_instruction_names(text) == {"sparse_paged_attention"}
    assert {"tpu.matmul", "tpu.enqueue_dma"} <= _mosaic_ops(text)
    assert " sort(" not in text


@pytest.mark.parametrize("tokens", [128, 100])
def test_compressed_key_scores(chip, tokens):
    """The score kernel at the cell's shapes: one tile of the packed
    rows, both groups' 16 heads stacked, 17 units of 16 table columns
    (the last one half: 2,112 keys); the Mosaic body copies 8 compressed
    keys a pair from the stack itself and multiplies on the MXU, and the
    step's walk beside it is built without a sort or a gather."""
    from neuronx_distributed_tpu.ops import sparse_attention as sp

    groups, rep, d, bs, cols, nb, layers = 2, 16, 128, 128, 264, 4224, 4
    spec = sp.SparseSpec()

    def scores(q, ck, layer, tables, q_pos):
        walk = sp.score_walk(tables, q_pos, spec, bs, rep, d, q.dtype,
                             force_pallas=True)
        return sp._key_scores_pallas(q, ck, layer, walk, bs // spec.stride,
                                     cols, 1.0 / math.sqrt(d)), walk.visits

    text = _assert_kernel_compiles(
        scores, chip((tokens, groups, rep, d), jnp.bfloat16),
        chip((layers, nb * 8, groups * d), jnp.bfloat16),
        chip((), jnp.int32), chip((tokens, cols), jnp.int32),
        chip((tokens,), jnp.int32))
    assert _kernel_instruction_names(text) == {"compressed_key_scores"}
    assert {"tpu.matmul", "tpu.enqueue_dma"} <= _mosaic_ops(text)
    assert " sort(" not in text and " gather(" not in text
    assert f"f32[1,2,16,{-(-tokens // 16) * 16},2112]" in text


def test_sparse_state_forward_writes_its_stacks_in_place(chip, on_one_chip):
    """The cell's layer pattern, widths and cache geometry (a narrow
    vocabulary): runs of 1, 6, 2, 4, 1 and 2 like layers, each a scan over
    the layer's index. No K/V, compressed-key or state stack is copied or
    changes layout on the way through the runs, no layer's weights are
    sliced out of their stack (a run of one layer included), and every
    stack is handed back in the buffer it came in."""
    import re

    from flax.core import meta

    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.models import minicpm_sala

    nb, bs, slots, cols, tokens, inter = 4224, 128, 16, 264, 128, 16384
    cfg = minicpm_sala.MiniCPMSALAConfig(
        hidden_size=4096, intermediate_size=inter, num_layers=16,
        vocab_size=512, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        mixer_types=minicpm_sala.PUBLISHED_MIXERS[9:25])
    assert [r[2] for r in cfg.runs()] == [1, 6, 2, 4, 1, 2]
    model = minicpm_sala.MiniCPMSALAForCausalLM(cfg)
    forward = cfg.serving_family().forward
    abstract = functools.partial(
        jax.tree_util.tree_map, lambda x: chip(x.shape, x.dtype))
    params = abstract(meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    cache = abstract(jax.eval_shape(lambda: paging.init_serving_cache(
        cfg, num_blocks=nb, block_size=bs, table_rows=slots,
        max_blocks_per_seq=cols, dtype=jnp.bfloat16)))

    def step(params, cache, tokens, positions, slot_ids):
        return forward(cfg, params, tokens, positions, cache,
                       slot_ids=slot_ids)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, chip((1, tokens), jnp.int32),
        chip((1, tokens), jnp.int32), chip((tokens,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"sparse_paged_attention",
                                               "compressed_key_scores"}

    # of the results one layer's states large or larger: none is a
    # layer's weights sliced out of their stack; a K/V stack is only ever
    # the scatter that writes rows where they lie, the state stack the
    # update of one layer's states where they lie
    large = _top_level_results(text, 32 * 16 * 128 * 128)
    writes = _in_place_writes(text, large)
    assert not [r for r in large if str(inter) in r[2]], large
    kv_stack, state_stack = f"bf16[4,{nb * 2 * bs},128]", \
        "f32[12,32,16,128,128]"
    assert [r for r in large if r[2] == kv_stack] == [
        r for r in writes if r[2] == kv_stack]
    assert sum(r[2] == kv_stack for r in writes) == 6   # K and V, 3 runs
    assert sum(r[2] == state_stack for r in large) == 3     # 3 runs
    assert all("dynamic-update-slice" in r[1] for r in large
               if r[2] == state_stack)
    # the compressed keys (66 MiB) are written in place too; what
    # else names their stack is the compiler's own prefetch of it into
    # VMEM ahead of the gather (asynchronous, sliced), not a change of
    # layout
    ck_stack = f"bf16[4,{nb * 8},256]"
    assert sum(r[2] == ck_stack for r in writes) == 3
    assert {r[0] for r in large if r[2] == ck_stack and r not in writes
            } <= {"copy-start", "copy-done", "slice-start", "slice-done",
                  "custom-call"}
    assert (compiled.memory_analysis().temp_size_in_bytes
            < cache.k.size * 2 / 4)          # one layer of K

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"4,{nb},2,{bs},128", f"4,{nb * 8},256",
                     f"12,32,{slots},128,128")]
    assert len(stacks) == 4
    assert set(stacks) <= aliased, (stacks, header)


# -- the latent cache (GLM-4.7-Flash): one row of 640 lanes a position -----------
# The cell glm-4.7-flash.serve-agentic: 128 rows, 20 heads over one row of
# a 512 latent and a 64 rotary key, 160 table columns, 4,096 blocks of 128.

def test_mla_paged_attention(chip):
    from neuronx_distributed_tpu.ops import mla_attention as mla

    tokens, heads, rank, bs, cols, nb, layers = 128, 20, 512, 128, 160, \
        4096, 7
    row = mla.row_width(rank, 64)
    assert row == 640 and mla.stacked_heads(heads) == 24
    fn = functools.partial(mla._mla_attention_pallas, rank=rank,
                           scale=1.0 / 16, interpret=False)
    text = _assert_kernel_compiles(
        fn, chip((tokens, heads, row), jnp.bfloat16),
        chip((layers, nb, bs, row), jnp.bfloat16), chip((nb, bs), jnp.int32),
        chip((tokens, cols), jnp.int32), chip((tokens,), jnp.int32),
        chip((), jnp.int32))
    # one Mosaic call, found by the benchmark's readers by this name
    assert _kernel_instruction_names(text) == {"mla_paged_attention"}
    assert {"tpu.matmul", "tpu.enqueue_dma"} <= _mosaic_ops(text)
    # at these widths a decode row's blocks run by eight (a ring of two
    # halves of 8 x 160 KiB) and a tile's shared blocks by four
    assert mla._unit_lengths(24, 8 * 24, row, bs, 2) == (8, 4, 192)


def test_latent_forward_writes_its_rows_in_place(chip, on_one_chip):
    """The cell's widths, depth and cache geometry (a narrow vocabulary):
    a run of one dense layer and a run of six expert layers over the one
    row stack. The stack is handed back in the buffer it came in and is
    only ever the scatter that writes rows where they lie; no layer's
    experts are sliced out of their stack; the kernel is the Pallas one."""
    import re

    from flax.core import meta

    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.models import glm_moe_lite

    nb, bs, slots, cols, tokens = 4096, 128, 64, 160, 128
    cfg = glm_moe_lite.GlmMoeLiteConfig(
        num_layers=7, vocab_size=512, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    assert cfg.runs() == (("dense", 0, 1), ("moe", 0, 6))
    model = glm_moe_lite.GlmMoeLiteForCausalLM(cfg)
    forward = cfg.serving_family().forward
    abstract = functools.partial(
        jax.tree_util.tree_map, lambda x: chip(x.shape, x.dtype))
    params = abstract(meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    cache = abstract(jax.eval_shape(lambda: paging.init_serving_cache(
        cfg, num_blocks=nb, block_size=bs, table_rows=slots,
        max_blocks_per_seq=cols, dtype=jnp.bfloat16)))

    def step(params, cache, tokens, positions, slot_ids):
        return forward(cfg, params, tokens, positions, cache,
                       slot_ids=slot_ids)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, chip((1, tokens), jnp.int32),
        chip((1, tokens), jnp.int32), chip((tokens,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"mla_paged_attention"}
    # of the results one expert's weights large or larger: none is a
    # layer's experts (or a part of them) out of their stack, and the row
    # stack is only ever written in place, once a run
    large = _top_level_results(text, 2048 * 1536)
    assert not [r for r in large if re.search(r"\[(64,)?2048,1536\]|"
                                              r"\[(64,)?1536,2048\]", r[2])]
    stack = f"bf16[7,{nb * bs},640]"
    assert [r for r in large if r[2] == stack] == [
        r for r in _in_place_writes(text, large) if r[2] == stack]
    assert sum(r[2] == stack for r in large) == 2
    assert (compiled.memory_analysis().temp_size_in_bytes
            < cache.rows.size * 2 / 7)       # one layer of rows
    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape == f"7,{nb},{bs},640"]
    assert len(stacks) == 1 and set(stacks) <= aliased, (stacks, header)
    # moe_expert_share_pct.batch finds the routed experts by the text of
    # an instruction as the profiler names it (result and operand types):
    # what it matches is what the compiler credits to the routed_experts
    # scope and nothing else, the three matmuls over the experts' stacks
    # among it (the down matmul's result type is o_proj's and the shared
    # expert's too)
    import json
    import os

    metric = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "layer_metrics", "moe_expert_share_pct.batch.json")))
    assert metric["reader"]["kind"] == "device_text_share"
    pattern = re.compile(metric["reader"]["match"])
    fusions = list(_fusions_as_the_profiler_names_them(text))
    hit = [(scope, shown) for scope, shown in fusions
           if pattern.search(shown)]
    assert hit and all("/routed_experts/" in scope for scope, _ in hit), hit
    streamed = [shown for scope, shown in fusions
                if re.search(r"bf16\[6,64,(2048,1536|1536,2048)\]", shown)]
    assert len(streamed) == 3 and all(pattern.search(x) for x in streamed)
    assert sum(" = bf16[1,128,2048]" in x for x in streamed) == 1
    big = [shown for scope, shown in fusions if "/routed_experts/" in scope
           and re.search(r"dot_general", scope)]
    assert big and all(pattern.search(x) for x in big), big


# -- the latent cache at two attentions a decoder layer (LongCat-Flash-Chat) -----
# The cell longcat-flash-chat.serve-agentic: 128 rows, 64 heads over one row
# of a 512 latent and a 64 rotary key, 160 table columns, 3,328 blocks of
# 128, 8 layers of rows for 4 double layers, 16 of 512 experts held.

def test_mla_paged_attention_at_64_heads(chip):
    """A tile of 8 rows x 64 heads (512 stacked rows) fits and tiles: a
    decode row's blocks run by eight over its own 64 stacked rows, a
    tile's shared blocks by four against slabs of 2 packed rows (float32
    scores [512, 128] are ``SCORE_BYTES`` already, so the tile is not
    scored whole; the packed rows' table rows ride in SMEM)."""
    from neuronx_distributed_tpu.ops import mla_attention as mla

    tokens, heads, rank, bs, cols, nb, layers = 128, 64, 512, 128, 160, \
        3328, 8
    row = mla.row_width(rank, 64)
    assert row == 640 and mla.stacked_heads(heads) == 64
    fn = functools.partial(mla._mla_attention_pallas, rank=rank,
                           scale=192 ** -0.5, interpret=False)
    text = _assert_kernel_compiles(
        fn, chip((tokens, heads, row), jnp.bfloat16),
        chip((layers, nb, bs, row), jnp.bfloat16), chip((nb, bs), jnp.int32),
        chip((tokens, cols), jnp.int32), chip((tokens,), jnp.int32),
        chip((), jnp.int32))
    assert _kernel_instruction_names(text) == {"mla_paged_attention"}
    assert {"tpu.matmul", "tpu.enqueue_dma"} <= _mosaic_ops(text)
    assert mla._unit_lengths(64, 8 * 64, row, bs, 2) == (8, 4, 128)


def test_double_layer_latent_step_at_the_published_widths(chip, topo,
                                                          on_one_chip):
    """The packed step of the cell's configuration file: it compiles for
    the chip with the latent kernel in it, holds what the configuration
    says it holds, writes the row stack in place (temporaries under a
    layer of rows), opens the scopes the double layer names and no
    fusion with a matmul inside reads another layer's."""
    import re

    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    config, models = _cell_config("longcat-flash-chat", None)
    assert set(config["reduced"]) == {"num_layers", "n_routed_experts",
                                      "vocab_size"}
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    nb = config["serve"]["num_blocks"]
    assert cache.rows.shape == (8, nb, 128, 640)
    assert cache.moe_counts.shape == (4,)
    layer = params["params"]["model"]["layers_double"]["layer"]
    assert layer["moe"]["experts"]["gate"].shape == (4, 16, 6144, 2048)
    assert layer["moe"]["router"]["kernel"].shape == (4, 6144, 768)
    assert layer["attn_1"]["k_up"].shape == (4, 64, 128, 512)

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    _STEP_TEXTS.setdefault(("longcat-flash-chat", None), text)
    assert _kernel_instruction_names(text) == {"mla_paged_attention"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params)) / gib
    assert 9.6 < weights < 9.7                       # 5,172.7M in bfloat16
    assert mem.temp_size_in_bytes < cache.rows.size * 2 / 8   # one layer's
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(held - aot["total"]) < 0.05 and 0.85 <= held / 15.75 <= 0.90
    assert abs(held / 15.75 - aot["of_chip"]) < 0.005

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape == f"8,{nb},128,640"]
    assert len(stacks) == 1 and set(stacks) <= aliased, (stacks, header)
    # no layer's experts or dense feed-forward is sliced out of its stack
    large = _top_level_results(text, 6144 * 2048)
    assert not [r for r in large if re.search(
        r"\[(16,)?(6144,2048|2048,6144)\]|\[(6144,12288|12288,6144)\]",
        r[2])]

    total, differ, kernels = scope_disagreements(text)
    assert total > 0 and kernels == {"attn.kernel"}
    top = [d for d in differ if d[1].split(".")[0] != d[2].split(".")[0]]
    assert sum(d[3] for d in top) <= 0.02 * total, top
    fusions, _ = _matmul_fusions(text)
    assert {scope_of(own) for _, own, _, _ in fusions} == {
        "attn.proj", "ffn.dense", "ffn.experts", "ffn.router", "head"}
    seen = {scope_of(m) for m in re.findall(r'op_name="([^"]*)"', text)}
    assert {"ffn.identity", "ffn", "attn.pool_write", "attn.walk", "norm",
            "embed", "sample"} <= seen


# -- the latent cache under contexts of 64k (Xing4.0-29B-A4B) ---------------------
# The cell xing4.0-29b-a4b.serve-longdocs: 128 rows, 32 heads over one row of
# a 512 latent and a 64 rotary key, 260 table columns of 256 positions, 2,080
# blocks, 7 layers of rows, a carry of 4 residual streams of 3,584.

def test_mla_paged_attention_at_32_heads_and_blocks_of_256(chip):
    """A tile of 8 rows x 32 heads (256 stacked rows) over blocks of 256
    positions: a decode row's blocks run by eight, a tile's shared blocks
    by two against slabs of 4 packed rows; the walk's four scalar arrays,
    8 rows x 260 columns a tile, are half the chip's SMEM. At blocks of
    128 the same contexts are 520 columns a row and the four arrays
    1.02 MiB of its 1 MiB: the compiler refuses the kernel by name."""
    from neuronx_distributed_tpu.ops import mla_attention as mla

    tokens, heads, rank, layers = 128, 32, 512, 7
    row = mla.row_width(rank, 64)
    fn = functools.partial(mla._mla_attention_pallas, rank=rank,
                           scale=0.14468, interpret=False)

    def operands(bs, cols, nb):
        return (chip((tokens, heads, row), jnp.bfloat16),
                chip((layers, nb, bs, row), jnp.bfloat16),
                chip((nb, bs), jnp.int32), chip((tokens, cols), jnp.int32),
                chip((tokens,), jnp.int32), chip((), jnp.int32))

    text = _assert_kernel_compiles(fn, *operands(256, 260, 2080))
    assert _kernel_instruction_names(text) == {"mla_paged_attention"}
    assert {"tpu.matmul", "tpu.enqueue_dma"} <= _mosaic_ops(text)
    assert mla._unit_lengths(32, 8 * 32, row, 256, 2) == (8, 2, 128)
    assert 4 * 4 * (16 * 8 * 260 + 2) < 2 ** 20 < 4 * 4 * (16 * 8 * 520 + 2)
    with pytest.raises(Exception, match="smem"):
        jax.jit(fn).lower(*operands(128, 520, 4160)).compile()


def test_residual_streams_latent_step_at_the_published_widths(chip, topo,
                                                              on_one_chip):
    """The packed step of the cell's configuration file: it compiles for
    the chip with the latent kernel in it, holds what the configuration
    says it holds, writes the row stack in place, and a sublayer's mixing
    is a handful of fusions: no loop, and no more instructions under the
    two scopes than a sixth of what the Sinkhorn rounds would be as
    reductions (95 a sublayer: AOT, PR 63)."""
    import re

    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    config, models = _cell_config("xing4.0-29b-a4b", None)
    assert set(config["reduced"]) == {"num_hidden_layers",
                                      "num_nextn_predict_layers"}
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    nb, bs = config["serve"]["num_blocks"], config["serve"]["block_size"]
    assert cache.rows.shape == (7, nb, bs, 640) and (nb, bs) == (2080, 256)
    assert cache.moe_counts.shape == (2,)
    moe = params["params"]["model"]["layers_moe"]["layer"]
    assert moe["moe"]["experts"]["gate"].shape == (5, 64, 3584, 1024)
    assert moe["attn"]["k_up"].shape == (5, 32, 128, 512)
    assert moe["hc_ffn"]["phi"].shape == (5, 4 * 3584, 24)
    assert moe["hc_ffn"]["phi"].dtype == jnp.float32

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"mla_paged_attention"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert count == 4_920_866_746
    assert mem.temp_size_in_bytes < cache.rows.size * 2 / 7   # one layer's
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(held - aot["total"]) < 0.05 and 0.85 <= held / 15.75 <= 0.90
    assert abs(held / 15.75 - aot["of_chip"]) < 0.005

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape == f"7,{nb},{bs},640"]
    assert len(stacks) == 1 and set(stacks) <= aliased, (stacks, header)

    # the mixing's instructions at the top level of a scan body: fusions
    # and the product with Phi, two kinds of layer x two sublayers
    top = [line for line in text.split("\n")
           if re.match(r"\s+(ROOT )?%?[\w.-]+ = ", line)
           and re.search(r" (fusion|convolution|custom-call|copy)\(", line)]
    mixing = [line for line in top if scope_of(
        re.search(r'op_name="([^"]*)"', line).group(1)
        if "op_name" in line else "").startswith("hc")]
    assert 4 <= len(mixing) <= 4 * 16, len(mixing)
    assert sum(" convolution(" in line for line in mixing) == 4
    seen = {scope_of(m) for m in re.findall(r'op_name="([^"]*)"', text)}
    assert {"hc.mix", "hc.apply", "ffn.experts", "ffn.shared", "ffn.dense",
            "attn.pool_write", "attn.walk", "norm", "embed",
            "sample"} <= seen


# -- latent attention over a learned selection (DeepSeek-V3.2) -------------------
# The cell deepseek-v3.2.serve-longdocs: 128 rows, 64 index heads of 128
# over index keys of 128, 260 table columns of 256 positions, 2,080 blocks,
# 5 layers of rows and of index keys.

def test_index_key_scores(chip):
    """A tile of 32 rows x 64 index heads (2,048 stacked rows) against one
    block of 256 keys a grid step, over a grid as long as the step's
    pairs: the kernel compiles for the chip with its product on the MXU
    and no copy of its own (the blocks ride the pipeline), and the walk's
    five scalar arrays fit SMEM at 66,560 positions a slot."""
    from neuronx_distributed_tpu.ops import indexed_attention as ia

    tokens, heads, width, bs, cols, nb, slots = 128, 64, 128, 256, 260, \
        2080, 8

    def fn(q, w, keys, tables, q_pos, layer):
        walk = ia.index_walk(tables, q_pos, bs, heads, width, slots, True)
        return ia._index_scores_pallas(q, w, keys, layer, q_pos, cols, walk,
                                       width ** -0.5, interpret=False)

    text = _assert_kernel_compiles(
        fn, chip((tokens, heads, width), jnp.bfloat16),
        chip((tokens, heads), jnp.float32),
        chip((5, nb, bs, width), jnp.bfloat16),
        chip((tokens, cols), jnp.int32), chip((tokens,), jnp.int32),
        chip((), jnp.int32))
    assert _kernel_instruction_names(text) == {"index_key_scores"}
    assert "tpu.matmul" in _mosaic_ops(text)
    assert ia.tile_rows(tokens, heads) == 32
    assert 5 * 4 * ia.max_pairs(tokens, heads, slots, cols) < ia.SMEM_BYTES
    # blocks of 128 at the same contexts: 520 columns a slot still fit
    assert 5 * 4 * ia.max_pairs(tokens, heads, slots, 520) < ia.SMEM_BYTES


def _scoped_ops(text):
    """The compiled text's sorts, gathers, scatters and custom calls by
    device scope."""
    import re

    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    by_scope = {}
    for line in text.split("\n"):
        found = re.search(
            r' (sort|gather|scatter|custom-call)\(.*op_name="([^"]*)"', line)
        if found:
            by_scope.setdefault(scope_of(found.group(2)), set()).add(
                found.group(1))
    return by_scope


def test_the_selection_alone_at_the_cells_width(chip):
    """The exact top-2,048 of 128 rows of 66,560 scores, compiled for the
    chip: no sort, no gather, no scatter and no kernel of the repo's; the
    32 rounds are one loop; the place-to-chunk one-hot ``[128, 520,
    2048]`` is an operand the compiler builds inside the product that
    reads it (272 MB in bf16 if it were written out)."""
    import re

    from neuronx_distributed_tpu.ops import indexed_attention as ia

    compiled = jax.jit(lambda s: ia.select_positions(s, 2048)).lower(
        chip((128, 66560), jnp.float32)).compile()
    text = compiled.as_text()
    assert not re.search(r" (sort|gather|scatter)\(", text)
    assert not _kernel_instruction_names(text)
    assert len(re.findall(r" while\(", text)) == 2      # the tie search's too
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_indexed_latent_step_at_the_published_widths(chip, topo,
                                                     on_one_chip):
    """The packed step of the cell's configuration file: it compiles for
    the chip with the score kernel in it and no latent kernel, holds what
    the configuration says it holds, writes both pool leaves in place,
    selects without a sort, a gather or a scatter and gathers the selected
    rows under ``attn.kernel``."""
    import re

    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    config, models = _cell_config("deepseek-v3.2", None)
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    nb, bs = config["serve"]["num_blocks"], config["serve"]["block_size"]
    assert (nb, bs, tokens) == (2080, 256, config["serve"]["token_budget"])
    assert cache.rows.shape == (5, nb, bs, 640)
    assert cache.index_keys.shape == (5, nb, bs, 128)
    assert cache.moe_counts.shape == (3,) and cache.counts.shape == (8,)
    moe = params["params"]["model"]["layers_moe"]["layer"]
    assert moe["moe"]["experts"]["gate"].shape == (4, 16, 7168, 2048)
    assert moe["moe"]["router"]["kernel"].shape == (4, 7168, 256)
    assert moe["attn"]["k_up"].shape == (4, 128, 128, 512)
    assert moe["attn"]["index_q_b"].shape == (4, 1536, 64 * 128)
    assert moe["attn"]["index_k_norm"]["bias"].shape == (4, 128)

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"index_key_scores"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert count == 4_635_518_208
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(mem.argument_size_in_bytes / gib - aot["arguments"]) < 0.05
    assert abs(mem.peak_memory_in_bytes / gib - aot["peak"]) < 0.1
    assert 0.25 <= mem.peak_memory_in_bytes / gib / 15.75 <= 0.90

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"5,{nb},{bs},640", f"5,{nb},{bs},128")]
    assert len(stacks) == 2 and set(stacks) <= aliased, (stacks, header)
    by_scope = _scoped_ops(text)
    assert "attn.select" not in by_scope
    assert "gather" in by_scope["attn.kernel"]
    assert "custom-call" in by_scope["attn.index"]
    seen = {scope_of(m) for m in re.findall(r'op_name="([^"]*)"', text)}
    assert {"attn.index", "attn.select", "attn.kernel", "attn.pool_write",
            "attn.walk", "ffn.experts", "ffn.shared", "ffn.router",
            "ffn.dense", "norm", "embed", "sample"} <= seen


# -- grouped GLU decode (MoE serving) at OLMoE's widths (ROADMAP R1): hidden
# 2048, expert width 1024. Mixtral's 4096 compiles too, in about ten seconds.

def test_grouped_glu_decode(chip):
    from neuronx_distributed_tpu.ops.blockwise_moe import (
        _grouped_glu_decode_pallas)

    e, h, i, block, block_i = 8, 2048, 1024, 128, 512
    fn = functools.partial(_grouped_glu_decode_pallas, block_size=block,
                           block_i=block_i, interpret=False)
    text = _assert_kernel_compiles(
        fn, chip((e * block, h), jnp.bfloat16),
        chip((e, h, i), jnp.bfloat16), chip((e, h, i), jnp.bfloat16),
        chip((e, i, h), jnp.bfloat16), chip((e,), jnp.int32))
    assert _kernel_instruction_names(text) == {"grouped_glu_fwd_decode"}


def _bank_shaped(hlo_text, e, h, i):
    """The first buffer of one layer's bank's shape, ``[E, H, I]`` or
    ``[E, I, H]`` bf16 (with or without a leading 1), or None: what a
    slice of the stacks written out for a custom call would be."""
    import re

    found = re.search(rf"bf16\[(?:1,)?{e},(?:{h},{i}|{i},{h})\][^\n]*",
                      hlo_text)
    return found and found.group(0)


def test_grouped_glu_over_the_layers_stacks(chip):
    """The stacked entry at ``sdar-30b-a3b-chat``'s widths inside a scan
    over the six layers' indices, as ``run_layers`` calls it: 13,312 rows
    in blocks of 64 (640 rows' 8 choices and a block of slack an expert),
    banks ``[6, 128, 2048, 768]``. Mosaic takes the squeezed leading
    dimension, the loop holds the kernel, and nothing of a bank's size is
    a temporary."""
    from neuronx_distributed_tpu.ops.blockwise_moe import _grouped_glu_pallas

    layers, e, h, i, block, rows = 6, 128, 2048, 768, 64, 13312

    def fn(xs, gate, up, down, be):
        def layer(x, l):
            # one tile of the whole width: ExpertMLPs' tile of 512 does
            # not divide 768
            return _grouped_glu_pallas(
                x, gate, up, down, be, block, i, False, e,
                layer=jax.lax.optimization_barrier(l)), None

        return jax.lax.scan(layer, xs, jnp.arange(layers, dtype=jnp.int32))[0]

    compiled = jax.jit(fn).lower(
        chip((rows, h), jnp.bfloat16),
        chip((layers, e, h, i), jnp.bfloat16),
        chip((layers, e, h, i), jnp.bfloat16),
        chip((layers, e, i, h), jnp.bfloat16),
        chip((rows // block,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"grouped_glu_fwd"}
    mem = compiled.memory_analysis()
    one_leaf = e * h * i * 2
    assert mem.temp_size_in_bytes < one_leaf // 4, mem.temp_size_in_bytes
    assert _bank_shaped(text, e, h, i) is None


# -- gate and up in a layer scan: the scan's slice fuses into the matmul ----
# A fused leaf with a 2 second from last is tiled T(2,128) on the chip; the
# matmul then cannot take the scan's dynamic-slice into its fusion, and XLA
# copies a layer's gate and up out of the stack first (PERF.md, PR 28).
# modules/glu.py stores two leaves, which compile clean; these cases hold
# every later form to that.

_PLUMBING = ("parameter", "get-tuple-element", "tuple", "while", "bitcast",
             "conditional", "call", "constant")


def _fusions_as_the_profiler_names_them(hlo_text):
    """``(op_name, text)`` of every fusion outside a fused computation:
    the scope path the compiler credits it to, and the instruction as a
    device trace's event names it, each operand with its type."""
    import re

    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", hlo_text))
    types, comp = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp, types = head.group(1), {}
            continue
        m = re.match(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(?[a-z0-9]+\[.*?) "
                     r"([a-z\-]+)\((.*?)\), ", line)
        if comp in fused or not m:
            continue
        name, result, opcode, operands = m.groups()
        types[name] = result
        if opcode != "fusion":
            continue
        scope = re.search(r'op_name="([^"]*)"', line)
        shown = ", ".join(f"{types.get(o, '?')} {o}"
                          for o in re.findall(r"%[\w.\-]+", operands))
        yield (scope.group(1) if scope else "",
               f"{name} = {result} fusion({shown}), kind=")


def _top_level_results(hlo_text, at_least):
    """``(opcode, name, shape)`` of every instruction outside a fused
    computation whose result has ``at_least`` elements or more: what the
    device runs as an operation of its own and writes to memory."""
    import re

    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", hlo_text))
    found, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(?[a-z0-9]+\[.*?) "
                     r"([a-z\-]+)\(", line)
        if comp in fused or not m or m.group(3) in _PLUMBING:
            continue
        for shape in re.finditer(r"[a-z0-9]+\[([\d,]+)\]", m.group(2)):
            if math.prod(map(int, shape.group(1).split(","))) >= at_least:
                found.append((m.group(3), m.group(1), shape.group(0)))
                break
    return found


def _stacked(chip, module, layers, *sample, dtype):
    """Abstract parameters of ``layers`` copies of ``module``, stacked on a
    leading dimension as the layer scan holds them."""
    from flax.core import meta

    shapes = meta.unbox(jax.eval_shape(module.init, jax.random.key(0),
                                       *sample))
    return jax.tree_util.tree_map(
        lambda s: chip((layers,) + s.shape, dtype), shapes)


def _dense_mlp(inter, dtype, param_dtype):
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaMLP

    return LlamaMLP(LlamaConfig(
        vocab_size=256, hidden_size=4096, intermediate_size=inter,
        num_layers=2, num_heads=32, num_kv_heads=8, max_seq_len=4096,
        dtype=dtype, param_dtype=param_dtype))


# (layer, rows of the packed step); Mixtral's bank at the capacity the
# serving cells run (factor 4.0: 128 slots an expert)
_GLU_SCANS = {"mistral": 14336, "evabyte": 11008, "mixtral_experts": 14336}


@pytest.mark.parametrize("which", list(_GLU_SCANS))
def test_layer_scan_reads_gate_and_up_in_place(chip, which):
    from neuronx_distributed_tpu.modules.moe import ExpertMLPs

    h, inter, rows = 4096, _GLU_SCANS[which], 128
    x = jnp.zeros((rows, h), jnp.bfloat16)
    if which == "mixtral_experts":
        experts = 8
        layer = ExpertMLPs(num_experts=experts, hidden_size=h,
                           intermediate_size=inter, top_k=2,
                           capacity_factor=4.0, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16)
        extra = (jnp.zeros((rows, 2), jnp.bfloat16),
                 jnp.zeros((rows, 2), jnp.int32))
        apply = lambda p, x, g, i: layer.apply(p, x, g, i)[0]
    else:
        experts, extra = 1, ()
        layer = _dense_mlp(inter, jnp.bfloat16, jnp.bfloat16)
        x = x[None]
        apply = layer.apply
    stacked = _stacked(chip, layer, 2, x, *extra, dtype=jnp.bfloat16)

    def step(stacked, x, *extra):
        def body(x, p):
            return x + apply(p, x, *extra), None
        return jax.lax.scan(body, x, stacked)[0]

    compiled = jax.jit(step).lower(
        stacked, *(chip(a.shape, a.dtype) for a in (x, *extra))).compile()
    gate_up_bytes = 2 * experts * h * inter * 2
    assert (compiled.memory_analysis().temp_size_in_bytes
            < gate_up_bytes / 4)
    # a whole layer's gate or up: no operation but a matmul reads one, and
    # a matmul's result is rows wide
    assert not _top_level_results(compiled.as_text(), experts * h * inter)


def test_train_scan_writes_gate_and_up_gradients_in_place(chip):
    """The train cell's per-chip shapes (tp=4: I 14,336 / 4, float32
    leaves, bf16 compute, full remat): each layer's dW matmul writes into
    the stacked gradient (``dynamic-update-slice`` fused in), with no
    per-layer ``copy`` of a gate or up leaf in front of it."""
    h, inter, layers = 4096, 3584, 2
    mlp = _dense_mlp(inter, jnp.bfloat16, jnp.float32)
    x = jnp.zeros((2, 4096, h), jnp.bfloat16)
    stacked = _stacked(chip, mlp, layers, x, dtype=jnp.float32)

    def loss(stacked, x):
        def body(x, p):
            return x + mlp.apply(p, x), None
        y, _ = jax.lax.scan(jax.checkpoint(body), x, stacked)
        return jnp.sum(y.astype(jnp.float32))

    def step(stacked, x):
        grads = jax.grad(loss)(stacked, x)
        return jax.tree_util.tree_map(lambda p, g: p - 1e-4 * g, stacked,
                                      grads)

    text = jax.jit(step, donate_argnums=(0,)).lower(
        stacked, chip(x.shape, x.dtype)).compile().as_text()
    # results shaped as a gate or up leaf, one layer's or the whole stack's
    leafs = [r for r in _top_level_results(text, h * inter)
             if r[2].endswith(f"{h},{inter}]")]
    assert not [r for r in leafs if r[0] == "copy"], leafs
    # gate's and up's gradients: two matmuls that end in the write
    assert sum("dynamic-update-slice" in name for _, name, _ in leafs) == 2, (
        leafs)


# -- device scopes: a fusion is one event and takes one scope ----------------
# A device trace's event is a top-level instruction of the compiled step,
# and its scope is the innermost marker of that instruction's own op_name
# (obs/device_scopes.py). XLA gives a fusion its root's metadata, so a
# matmul fused into another layer's epilogue would read as that layer.
# These cases compile the benchmark's steps at the cells' widths and
# geometry (a shallower stack where the layers are one scan body) and hold
# every fusion with a matmul inside to the scope of its heaviest one.

# cell's configuration, layers compiled (None: the cell's own)
_SCOPED_STEPS = {"mistral-7b-serve": 2, "mixtral-8x7b": 2, "evabyte-6.5b": 2,
                 "minicpm-sala-9b": None, "glm-4.7-flash": None,
                 "longcat-flash-chat": None, "mistral-7b": 2}


def _computation_bodies(hlo_text):
    """``{computation: [its lines]}`` of a compiled program's text."""
    bodies, comp = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = bodies.setdefault(head.group(1), [])
        elif comp is not None:
            comp.append(line)
    return bodies


def _matmul_fusions(hlo_text):
    """``(name, own op_name, heaviest matmul's op_name, its largest
    operand's elements)`` of every top-level fusion whose body holds a
    ``dot`` or a ``convolution``, and ``(name, op_name)`` of every
    top-level custom call (a Mosaic kernel)."""
    import re

    bodies = _computation_bodies(hlo_text)
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", hlo_text))

    def op_name(line):
        m = re.search(r'op_name="([^"]*)"', line)
        return m.group(1) if m else ""

    def heaviest(body):
        types, best = {}, (0, None)
        for line in body:
            m = re.match(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(?[a-z0-9]+\[.*?) "
                         r"([a-z\-]+)\((.*?)\)[,\n]", line + "\n")
            if not m:
                continue
            name, result, opcode, operands = m.groups()
            types[name] = result
            if opcode not in ("dot", "convolution"):
                continue
            size = max(math.prod(map(int, dims.split(",")))
                       for o in re.findall(r"%[\w.\-]+", operands)
                       for dims in re.findall(r"\[([\d,]+)\]",
                                              types.get(o, "[1]"))[:1])
            if size > best[0]:
                best = (size, op_name(line))
        return best

    fusions, kernels = [], []
    for comp, body in bodies.items():
        if comp in fused:
            continue
        for line in body:
            call = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
            name = re.match(r"^\s*(?:ROOT )?(%[\w.\-]+) = ", line)
            if call and call.group(1) in bodies:
                size, inner = heaviest(bodies[call.group(1)])
                if inner is not None:
                    fusions.append((name.group(1), op_name(line), inner,
                                    size))
            elif name and 'custom_call_target="tpu_custom_call"' in line:
                kernels.append((name.group(1), op_name(line)))
    return fusions, kernels


def _cell_config(config_name, layers):
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import harness
    from runners import models

    config = harness.read_json(os.path.join(bench, "configs",
                                            config_name + ".json"))
    if layers is not None:
        config = dict(config, num_hidden_layers=layers)
    return config, models


def _packed_step(chip, cfg, forward, params, cache, tokens):
    """The packed serving step as the engine builds it (forward and
    sampling, the cache donated) over :func:`_serving_parts`, compiled
    for the described chip."""
    from neuronx_distributed_tpu.inference.sampling import (SamplingConfig,
                                                            sample)
    from neuronx_distributed_tpu.obs.device_scopes import device_scope

    def step_fn(params, cache, tokens, positions, slot_ids, rng):
        logits, cache = forward(cfg, params, tokens, positions, cache,
                                slot_ids=slot_ids)
        with device_scope("sample"):
            return sample(logits[0], rng, SamplingConfig()), cache

    rng = jax.eval_shape(lambda: jax.random.key(0))
    return jax.jit(step_fn, donate_argnums=(1,)).lower(
        params, cache, chip((1, tokens), jnp.int32),
        chip((1, tokens), jnp.int32), chip((tokens,), jnp.int32),
        chip(rng.shape, rng.dtype)).compile()


def _serving_parts(chip, config, models):
    """``(cfg, forward, params, cache, width)`` of a serving cell, the
    arrays abstract and on the described chip."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference import paging

    s = config["serve"]
    dtype = models.dtype_of(s["dtype"])
    cfg, model, forward = models.build(config, dtype=dtype,
                                       param_dtype=dtype,
                                       **s.get("model", {}))
    abstract = functools.partial(
        jax.tree_util.tree_map, lambda x: chip(x.shape, x.dtype))
    params = abstract(meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    cache = abstract(jax.eval_shape(lambda: paging.init_serving_cache(
        cfg, num_blocks=s["num_blocks"], block_size=s["block_size"],
        table_rows=s["max_slots"],
        max_blocks_per_seq=s["max_blocks_per_seq"], dtype=dtype)))
    return cfg, forward, params, cache, s["token_budget"]


_STEP_TEXTS = {}


def _scoped_step(chip, topo, config_name, layers):
    """The compiled text of a cell's step: the packed serving step as the
    engine builds it (forward and sampling, the cache donated), or the
    train step of ``make_train_step`` over the described 2x2. Compiled
    once for the cases that read it."""
    if (config_name, layers) not in _STEP_TEXTS:
        _STEP_TEXTS[config_name, layers] = _compile_scoped_step(
            chip, topo, config_name, layers)
    return _STEP_TEXTS[config_name, layers]


def _compile_scoped_step(chip, topo, config_name, layers):
    config, models = _cell_config(config_name, layers)
    if config["runner"] == "train":
        return _cell_train_step(topo, config_name, layers).as_text()
    return _packed_step(chip, *_serving_parts(chip, config, models)
                        ).as_text()


_TRAIN_STEPS = {}


def _cell_train_step(topo, config_name, layers, **model_kw):
    """A training cell's compiled step, once for the cases that read it."""
    key = (config_name, layers, tuple(sorted(model_kw.items())))
    if key not in _TRAIN_STEPS:
        _TRAIN_STEPS[key] = _scoped_train_step(
            topo, *_cell_config(config_name, layers), **model_kw)
    return _TRAIN_STEPS[key]


@contextlib.contextmanager
def _train_parts(topo, config, models, **model_kw):
    """``(pm, tx, state, shardings, batch)`` of the cell's train step as
    shapes placed on the described 2x2, the mesh up while the block
    runs; ``model_kw`` replaces fields of the configured model."""
    import dataclasses

    import neuronx_distributed_tpu as nxd
    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec

    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import optimizer as opt_mod
    from neuronx_distributed_tpu.trainer import trainer

    s = config["train"]
    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=s["tensor_parallel_size"],
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=s["zero1"]),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode=s["activation_checkpoint"]),
        sequence_parallel=s["sequence_parallel"], devices=topo.devices)
    try:
        seq, batch = 4096, 2
        base, module, _ = models.build(
            config, max_seq_len=seq,
            dtype=models.dtype_of(s["compute_dtype"]),
            param_dtype=models.dtype_of(s["param_dtype"]),
            use_flash_attention=s["flash_attention"])
        model = type(module)(dataclasses.replace(
            nxd.configure_model(cfg, base), **model_kw))
        mesh = ps.get_mesh()
        boxed = jax.eval_shape(model.init, jax.random.key(0),
                               jnp.zeros((batch, seq), jnp.int32))
        specs = trainer._spec_tree(boxed)
        shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape),
                                        meta.unbox(boxed))
        pm = trainer.ParallelModel(module=model, config=cfg,
                                   param_specs=specs, param_shapes=shapes)
        is_spec = dict(is_leaf=lambda x: isinstance(x, PartitionSpec))
        to_shard = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            ps.named_sharding_for_spec, tree, **is_spec)
        placed = lambda shapes, shard: jax.tree_util.tree_map(  # noqa: E731
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh), shapes, shard)
        params = placed(meta.unbox(boxed), to_shard(specs))
        tx = opt_mod.make_optimizer(cfg, learning_rate=s["learning_rate"],
                                    weight_decay=0.01)
        opt_shape = jax.eval_shape(tx.init, params)
        opt_shard = to_shard(opt_mod.zero1_state_specs(
            opt_shape, specs, shapes, enabled=s["zero1"]))
        everywhere = NamedSharding(mesh, PartitionSpec())
        state = trainer.TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=everywhere),
            params=params,
            opt_state=jax.jit(tx.init, out_shardings=opt_shard).eval_shape(
                params),
            comm_error=None)
        shardings = trainer.TrainState(
            step=everywhere, params=to_shard(specs), opt_state=opt_shard,
            comm_error=None)
        ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                   sharding=everywhere)
        yield pm, tx, state, shardings, {"input_ids": ids, "labels": ids}
    finally:
        ps.destroy_model_parallel()


def _scoped_train_step(topo, config, models, **model_kw):
    """The cell's train step, compiled for the described 2x2."""
    from neuronx_distributed_tpu.trainer import trainer

    with _train_parts(topo, config, models, **model_kw) as (
            pm, tx, state, shardings, batch):
        return trainer.make_train_step(pm, tx, shardings).lower(
            state, batch).compile()


def scope_disagreements(hlo_text):
    """``(matmul elements in all, [(fusion, own scope, heaviest matmul's
    scope, elements)] where the two differ, kernels' scopes)`` of a
    compiled step."""
    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    fusions, kernels = _matmul_fusions(hlo_text)
    differ = [(name, scope_of(own), scope_of(inner), size)
              for name, own, inner, size in fusions
              if scope_of(own) != scope_of(inner)]
    return (sum(f[3] for f in fusions), differ,
            {scope_of(path) for _, path in kernels})


@pytest.mark.parametrize("config_name", list(_SCOPED_STEPS))
def test_a_fusion_reads_the_layer_of_its_heaviest_matmul(
        chip, topo, on_one_chip, monkeypatch, config_name):
    from neuronx_distributed_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    text = _scoped_step(chip, topo, config_name, _SCOPED_STEPS[config_name])
    total, differ, kernels = scope_disagreements(text)
    # (the sparse layers' selection scores its keys in a kernel of its own)
    assert total > 0 and kernels == {"attn.kernel"} | (
        {"attn.select"} if config_name == "minicpm-sala-9b" else set())
    top = [d for d in differ
           if d[1].split(".")[0] != d[2].split(".")[0]]
    # a layer (attn, ffn, head..) misread for at most 2% of the matmuls'
    # parameters; a child misread within its layer is PERF.md section 7's
    assert sum(d[3] for d in top) <= 0.02 * total, top


def _kernels_by_computation(hlo_text):
    """``{computation: [kernel instruction stems]}`` of a compiled
    program: a ``while`` body is one computation, and the layer scan's
    forward and backward are two."""
    found, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
        elif name and 'custom_call_target="tpu_custom_call"' in line:
            found.setdefault(name, []).extend(_kernel_instruction_names(line))
    return found


_ITEMSIZE = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}


def _carried(hlo_text):
    """``Counter({element type: n})`` of the largest tuple a ``while`` of
    the program carries: the backward layer scan's, which holds what the
    forward scan stacked."""
    import collections

    def tuple_bytes(shape):
        return sum(_type_bytes(t) for t in re.findall(r"\w+\[[\d,]*\]",
                                                      shape))
    largest = max((m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%\S+ = (\(.*?\)) while\(", hlo_text, re.M)),
        key=tuple_bytes)
    return collections.Counter(re.findall(r"\w+\[[\d,]*\]", largest))


def _type_bytes(element_type):
    dtype, dims = re.match(r"(\w+)\[([\d,]*)\]", element_type).groups()
    return _ITEMSIZE[dtype] * math.prod(int(n) for n in dims.split(",") if n)


def _stacked_more(more, fewer, layers):
    """``{element type: n}``: the stacks a layer (leading dimension
    ``layers``) that the program ``more`` carries over ``fewer``. Since
    PR 65 the bound step's layers run rings whose buffers the compiler
    hoists in and out of the carry by policy, a few blocks of 4-16 MiB
    either way, so what a policy keeps is read from the stacks by name
    and not from the tuple's sum."""
    gained = _carried(more.as_text()) - _carried(fewer.as_text())
    return {t: n for t, n in gained.items()
            if t.split("[")[1].startswith(f"{layers},") and t.count(",") > 2}


@pytest.fixture
def train_steps(chip, topo, monkeypatch):
    """``train_steps(**model_kw)``: ``mistral-7b``'s train step at two
    layers, compiled for the described 2x2 with the flash dispatcher
    steered to the compiled kernel."""
    from neuronx_distributed_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    return functools.partial(_cell_train_step, topo, "mistral-7b",
                             _SCOPED_STEPS["mistral-7b"])


@pytest.mark.parametrize("policy", [None, "nothing"],
                         ids=["default", "nothing"])
def test_train_step_recomputes_no_flash_forward(train_steps, policy):
    """Full checkpointing as the cell asks for it keeps the kernel's
    output and log-sum-exp: ``flash_attention_fwd`` is in the forward
    scan's body and not beside ``_bwd_dq`` and ``_bwd_dkv`` in the
    backward's. ``remat_policy="nothing"`` runs it in both."""
    text = train_steps(**({"remat_policy": policy} if policy else {})
                       ).as_text()
    bodies = sorted(sorted(k) for k in _kernels_by_computation(text).values())
    backward = ["flash_attention_bwd_dkv", "flash_attention_bwd_dq"]
    assert bodies == [backward + ["flash_attention_fwd"] * bool(policy),
                      ["flash_attention_fwd"]], bodies


def test_train_step_holds_the_flash_output_and_log_sum_exp(train_steps):
    """What the default keeps over ``"nothing"``: a chip's share of the
    kernel's output, 2 x 4,096 x 8 x 128 bf16, and of its log-sum-exp,
    2 x 8 x 4,096 float32, 16.25 MiB a layer, stacked by the forward scan
    and carried by the backward's. The program's peak grows by little
    more than that: the backward body no longer holds the forward kernel's
    working set."""
    layers, mib = _SCOPED_STEPS["mistral-7b"], 2 ** 20
    kept, nothing = train_steps(), train_steps(remat_policy="nothing")
    stacks = _stacked_more(kept, nothing, layers)
    assert stacks == {f"bf16[{layers},2,4096,8,128]": 1,
                      f"f32[{layers},2,8,4096]": 1}, stacks
    assert sum(map(_type_bytes, stacks)) / layers / mib == 16.25
    # the peak grows by the pair and one block of the sharded stream that
    # the compiler then carries through the loop (16 MiB at any depth)
    grown = (kept.memory_analysis().peak_memory_in_bytes
             - nothing.memory_analysis().peak_memory_in_bytes) / mib
    assert grown <= 1.1 * 16.25 * layers + 16, grown


_GLU_KEPT = "save_attention_and_glu"
# gate's and up's products of a layer on a chip: 2 x [2, 4096, 3584] bf16
_GLU_PAIR_MIB = 2 * 2 * 4096 * 3584 * 2 / 2 ** 20


def _products_by_computation(hlo_text, result):
    """``{computation: n}``: the matmuls with a result of shape ``result``
    that a computation runs, its own and those of the fusions it calls
    (a ``while`` body is one computation)."""
    bodies = _computation_bodies(hlo_text)
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", hlo_text))
    made = re.compile(r"= %s\S* (?:convolution|dot)\(" % re.escape(result))
    own = {name: sum(bool(made.search(line)) for line in body)
           for name, body in bodies.items()}
    found = {}
    for name, body in bodies.items():
        if name in fused:
            continue
        n = own[name] + sum(
            own[c] for line in body
            for c in re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", line))
        if n:
            found[name] = n
    return found


@pytest.mark.parametrize("policy,recomputed", [(None, 2), (_GLU_KEPT, 0)],
                         ids=["lean", "rich"])
def test_train_step_recomputes_gate_and_up_only_without_the_bytes(
        train_steps, policy, recomputed):
    """The feed-forward's products of width 3,584 (14,336 over tp=4): the
    forward scan's body runs gate and up; the backward's runs ``down``'s
    transpose, and gate and up again only where the layer did not keep
    them. A described device reports no memory limit, so the step that
    names no policy is the lean one."""
    text = train_steps(**({"remat_policy": policy} if policy else {})
                       ).as_text()
    # since PR 65 the stream is sharded over the sequence and each of the
    # three is an all-gather ring's four block products of 1,024 rows
    products = sorted(_products_by_computation(
        text, "bf16[2,1024,3584]").values())
    assert products == sorted([4 * 2, 4 * (1 + recomputed)]), products


@pytest.mark.parametrize("what", ["carried", "peak"])
def test_train_step_holds_gate_and_up_products(train_steps, what):
    """What ``save_attention_and_glu`` keeps over the default: 112 MiB a
    layer and chip, stacked by the forward scan and carried by the
    backward's; the program's peak (arguments and temporaries as the
    compiler lays them out, what it refuses a program by) grows by that
    less one pair (52 MiB a layer at two layers; 11.71 -> 12.81 GiB at
    eleven: AOT, PR 65). ``temp_size_in_bytes`` is not that number: it
    counts what one scan hands the other in both."""
    layers, mib = _SCOPED_STEPS["mistral-7b"], 2 ** 20
    lean, rich = train_steps(), train_steps(remat_policy=_GLU_KEPT)
    if what == "carried":
        stacks = _stacked_more(rich, lean, layers)
        assert stacks == {f"bf16[{layers},2,4096,3584]": 2}, stacks
        assert 2 * _type_bytes(*stacks) / layers / mib == _GLU_PAIR_MIB
    else:
        # the lean step's backward body holds the recomputed pair where
        # the peak is, and the rich step's reads a row of the stack there:
        # a pair less of temporaries at any depth (PR 65: the rings' block
        # buffers are the compiler's to place; the flat products of the
        # replicated stream were not, and the peak grew by the whole pair)
        grown = (rich.memory_analysis().peak_memory_in_bytes
                 - lean.memory_analysis().peak_memory_in_bytes) / layers / mib
        assert (0.9 * _GLU_PAIR_MIB * (layers - 1) / layers <= grown
                <= 1.2 * _GLU_PAIR_MIB), grown


def test_train_step_rings_write_each_block_product_once(train_steps):
    """The bound step shards the residual stream over the sequence, so
    every projection of a layer is a ring, and an all-gather ring's output
    is its block buffer ``[2, 4, 1024, f]`` (``ops/collective_matmul``):
    the compiled step updates it only from fusions that hold the block's
    matmul (the product is written where it stays), never by an update or
    a copy of its own, and nothing of a flat ring output's shape is
    updated at all. Until PR 65 the flat output was, and XLA copied it
    whole at every hop (``dynamic_update_slice bf16[2,4096,3584]`` 26 ms a
    step on the chip). No layer's forward all-gathers the residual."""
    text = train_steps().as_text()
    bodies = _computation_bodies(text)
    fused = set(re.findall(r"fusion\([^\n]*calls=%([\w.\-]+)", text))
    updates = 0
    for name, body in bodies.items():
        for line in body:
            made = re.match(
                r"\s*(?:ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(",
                line)
            if not made:
                continue
            result, op = made.groups()
            if op == "dynamic-update-slice" and re.match(
                    r"bf16\[2,4096,(3584|1024|256)\]", result):
                raise AssertionError(line)
            if not re.match(r"bf16\[2,4,1024,\d+\]", result):
                continue
            if name in fused:
                if op == "dynamic-update-slice":
                    # the update's operand is the block's matmul
                    assert any("convolution(" in l or " dot(" in l
                               for l in body), name
                    updates += 1
            else:
                assert op not in ("dynamic-update-slice", "copy"), line
    # two layers' worth of one program: q, k, v, gate, up forward and
    # again where they are recomputed, o_proj's and down's input
    # gradients: four blocks a ring
    assert updates >= 4 * (5 + 5 + 2), updates
    forward = min((b for b in bodies.values() if any(
        "flash_attention_fwd" in l for l in b) and not any(
            "flash_attention_bwd" in l for l in b)), key=len)
    assert not any(re.search(r"= bf16\[2,4096,4096\]\S* all-gather", l)
                   for l in forward)


@pytest.mark.parametrize("layers,accum,chosen", [
    (11, 1, _GLU_KEPT), (12, 1, "save_attention"), (13, 1, "save_attention"),
    (11, 2, "save_attention"), (9, 2, _GLU_KEPT)],
    ids=["11", "12", "13", "11-accumulating", "9-accumulating"])
def test_the_train_step_keeps_gate_and_up_where_a_chip_has_the_bytes(
        topo, monkeypatch, layers, accum, chosen):
    """The rule at the cell's own bytes (``utils/remat.py``): on a chip
    of 15.75 GiB the 11-layer job keeps the pair and the same job at 12
    and 13 layers does not; summing two microbatches' gradients into an
    accumulator, a second copy of them (2.48 GiB at 11 layers beside
    0.60 fewer kept), the 11-layer job does not and the 9-layer one
    does (AOT, PR 61: the rich step then peaks at 14.60 and 12.18 GiB).
    The state a chip holds is counted as the compiler counts the step's
    arguments."""
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import trainer

    monkeypatch.setattr(trainer, "memory_limit_bytes",
                        lambda devices: 16909334528)
    with _train_parts(topo, *_cell_config("mistral-7b", layers)) as (
            pm, _, state, shardings, _):
        held = trainer._bytes_a_chip(state, shardings)
        # AOT's argument_size_in_bytes: 7.4547 GiB at 11 layers, 8.6734 at 13
        assert abs(held / 2 ** 30 - (0.7516 + 0.609375 * layers)) < 1e-3
        module = trainer._module_for_step(
            pm, ps.get_mesh(), state, shardings, (2 // accum, 4096),
            accum > 1)
    assert module.cfg.remat_policy == chosen


def test_a_described_chip_reports_no_limit(topo):
    """No process holds a described topology's devices: an AOT compile
    traces the step that keeps ``save_attention``."""
    from neuronx_distributed_tpu.utils.device import memory_limit_bytes

    assert memory_limit_bytes(topo.devices) is None


def test_the_sparse_step_scores_its_compressed_keys_in_the_pool(
        chip, topo, on_one_chip, monkeypatch):
    """The packed step of ``minicpm-sala.serve-longdocs`` (128 rows, 264
    table columns of 8 compressed keys): no row's whole table of keys is
    gathered (``bf16[270336,256]``: 128 x 2,112 rows of both groups'
    keys), and every run of sparse layers holds one
    ``compressed_key_scores`` call under ``attn.select`` beside its
    ``sparse_paged_attention`` under ``attn.kernel``."""
    from neuronx_distributed_tpu.obs.device_scopes import scope_of
    from neuronx_distributed_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    text = _scoped_step(chip, topo, "minicpm-sala-9b", None)
    assert "bf16[270336,256]" not in text and "[128,2112,256]" not in text
    _, kernels = _matmul_fusions(text)
    by_scope = {}
    for name, path in kernels:
        by_scope.setdefault(scope_of(path), []).append(
            name.lstrip("%").split(".")[0])
    assert set(by_scope) == {"attn.select", "attn.kernel"}
    assert set(by_scope["attn.select"]) == {"compressed_key_scores"}
    assert set(by_scope["attn.kernel"]) == {"sparse_paged_attention"}
    config, models = _cell_config("minicpm-sala-9b", None)
    cfg = models.build(config)[0]
    runs = sum(kind == "sparse" for kind, _, _ in cfg.runs())
    assert len(by_scope["attn.select"]) == runs == len(
        by_scope["attn.kernel"])
    # the scores a layer: [1 tile, 2 groups, 16 heads, 128 rows, 2112 keys]
    assert "f32[1,2,16,128,2112]" in text


# -- the state-pool cache (Granite-4.0-H-Micro): the whole model a chip ----------
# The cell granite-4.0-h-micro.serve-longgen: 128 rows, 80 slots, 36 mamba
# layers over float32 states [36, 80, 128, 4096] and tails [36, 3, 80,
# 4352], 4 attention layers of 32 heads of 64 over a pool [4, 1600, 128, 4,
# 128] (two K/V heads a row), the vocabulary of 100,352 tied to the head.

def test_state_pool_step_at_the_published_widths(chip, topo, on_one_chip,
                                                 monkeypatch):
    """The packed step of the cell's configuration file, uncut: it
    compiles for the chip with both kernels in it (the paged kernel on
    heads of 64, the scan's state update), holds what the configuration
    says it holds, leaves no stack copied (temporaries under one layer's
    states), hands every stack back in the buffer it came in, and every
    fusion with a matmul inside reads the scope of its heaviest one."""
    import re

    from neuronx_distributed_tpu.ops import ssd

    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    config, models = _cell_config("granite-4.0-h-micro", None)
    assert config["reduced"] == {} and config["num_hidden_layers"] == 40
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    slots = config["serve"]["max_slots"]
    assert cache.k.shape == (4, slots * 20, 128, 4, 128)
    assert cache.states["ssm"].shape == (36, slots, 128, 4096)
    assert cache.states["conv"].shape == (36, 3, slots, 4352)

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"paged_attention",
                                               "ssd_state_update"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params)) / gib
    assert 5.9 < weights < 6.0                       # 3,191M in bfloat16
    assert mem.temp_size_in_bytes < slots * 128 * 4096 * 4   # one layer's
    assert 13.0 < held < 13.5, held              # of 15.75: the file's 84%
    assert abs(held - config["assumed"]["serve_aot_gib"]["total"]) < 0.05

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"4,{slots * 20},128,4,128", f"36,{slots},128,4096",
                     f"36,3,{slots},4352")]
    assert len(stacks) == 4 and set(stacks) <= aliased, (stacks, header)

    total, differ, kernels = scope_disagreements(text)
    assert total > 0 and kernels == {"attn.kernel", "attn.state"}
    top = [d for d in differ if d[1].split(".")[0] != d[2].split(".")[0]]
    assert sum(d[3] for d in top) <= 0.02 * total, top


# -- the window-pool cache (Laguna-S-2.1): two pools, two head counts -----------
# The cell laguna-s-2.1.serve-agentic: 128 rows, 64 slots; 2 full layers of
# 48 heads over a pool of 160 columns a slot, 3 sliding layers of 72 heads
# over rings of 5 blocks a slot, 8 K/V heads of 128 under both; 128 of 256
# experts held, half the vocabulary.

@pytest.mark.parametrize("n_rep,cols,sliding", [(6, 160, None), (9, 5, 512)],
                         ids=["full-48-heads", "sliding-72-heads"])
def test_paged_attention_at_two_head_counts(chip, n_rep, cols, sliding):
    """The kernel at ``n_rep`` 6 (tiles of 20 rows) and, with a causal
    window over a slot's ring, at ``n_rep`` 9 (tiles of 8 rows), which is
    then ``swa_attention`` in a trace. At both a decode row's narrow
    product is 16 stacked rows from the whole sublane its first head lies
    in: a 16-row slice of the bf16 queries (16 rows a vreg) at a start
    that is a multiple of 8 and not of 16, which Mosaic takes."""
    from neuronx_distributed_tpu.ops.paged_attention import (
        _paged_attention_pallas, narrow_rows, run_blocks, tile_rows)

    tokens, kv, d, bs, layers = 128, 8, 128, 128, 2
    nb = 320 if sliding else 3072
    assert tile_rows(n_rep, tokens) * n_rep in (120, 72)
    assert narrow_rows(n_rep) == 16
    pool = chip((layers, nb, bs, kv, d), jnp.bfloat16)
    # runs of four blocks of a group whose rows name them block by block
    # (neighbouring rows' groups overlap at 6 and 9 heads)
    assert run_blocks(pool, pool, n_rep, cols) == 4
    fn = functools.partial(_paged_attention_pallas,
                           scale=1.0 / math.sqrt(d), interpret=False,
                           sliding=sliding)
    text = _assert_kernel_compiles(
        fn, chip((tokens, kv * n_rep, d), jnp.bfloat16), pool, pool,
        chip((nb, bs), jnp.int32), chip((tokens, cols), jnp.int32),
        chip((tokens,), jnp.int32), chip((), jnp.int32), None, None)
    assert _kernel_instruction_names(text) == {
        "swa_attention" if sliding else "paged_attention"}
    assert {"tpu.matmul", "tpu.enqueue_dma", "tpu.strided_load"} <= (
        _mosaic_ops(text))


def test_window_pool_step_at_the_published_widths(chip, topo, on_one_chip):
    """The packed step of the cell's configuration file: it compiles for
    the chip with both kernels in it, holds what the configuration says
    it holds, leaves no pool copied, hands both pools back in the buffers
    they came in, and every fusion with a matmul inside reads the scope
    of its heaviest one."""
    import re


    config, models = _cell_config("laguna-s-2.1", None)
    assert sorted(config["reduced"]) == [
        "gating_types", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "num_hidden_layers",
        "vocab_size"]
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    s = config["serve"]
    slots, blocks = s["max_slots"], s["num_blocks"]
    assert cache.k.shape == (2, blocks, 128, 8, 128)
    assert cache.wk.shape == (3, slots * 5, 128, 8, 128)
    assert cache.wpos.shape == (slots * 5, 128)
    tree = params["params"]["model"]
    assert tree["layers_sliding_sparse"]["layer"]["moe"]["experts"][
        "down"].shape == (3, 128, 1024, 3072)
    assert tree["layers_sliding_sparse"]["layer"]["moe"]["router"][
        "kernel"].shape == (3, 3072, 256)
    assert tree["layers_sliding_sparse"]["layer"]["attn"]["qkv"][
        "q_kernel"].shape == (3, 3072, 9216)
    assert params["params"]["lm_head"]["kernel"].shape == (3072, 50176)

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"paged_attention",
                                               "swa_attention"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params)) / gib
    assert 10.3 < weights < 10.45                    # 5,572M in bfloat16
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(weights - aot["weights"]) < 0.01
    assert abs(mem.temp_size_in_bytes / gib - aot["temporaries"]) < 0.05
    assert abs(held - aot["total"]) < 0.05, (held, mem)
    assert held > 0.8 * 15.75                        # the file's share

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"2,{blocks},128,8,128", f"3,{slots * 5},128,8,128")]
    assert len(stacks) == 4 and set(stacks) <= aliased, (stacks, header)

    total, differ, kernels = scope_disagreements(text)
    assert total > 0 and kernels == {"attn.kernel.full",
                                     "attn.kernel.window"}
    top = [d for d in differ if d[1].split(".")[0] != d[2].split(".")[0]]
    assert sum(d[3] for d in top) <= 0.02 * total, top


# -- keys of 192 beside values of 128: a wide-key pool, a sink term ----------

@pytest.mark.parametrize("kv,cols,nb,sliding", [(4, 256, 10240, None),
                                                (8, 2, 256, 128)],
                         ids=["full-gqa16", "sliding-gqa8-sink"])
def test_paged_attention_at_wide_keys(chip, kv, cols, nb, sliding):
    """The kernel at MiMo-V2-Flash's widths: 64 query heads of 192 over 4
    K/V heads (a full layer: tiles of 8 rows, a decode row's narrow
    product 16 stacked rows) and over 8 under a window of one block with a
    sink a head (a ring of 2: tiles of 16 rows, a narrow product of 8).
    The K pool is rows of ``KV * 192`` values in whole 128-lane chunks,
    which the kernel reads as aligned lane slices of the block; V is by
    head, 128 wide, as every other pool."""
    from neuronx_distributed_tpu.ops.paged_attention import (
        _paged_attention_pallas, narrow_rows, run_blocks, tile_rows)

    tokens, n, d, dv, bs, layers = 128, 64, 192, 128, 128, 2
    assert tile_rows(n // kv, tokens) * (n // kv) == 128
    assert narrow_rows(n // kv) == n // kv
    # a full layer's block is 320 KiB and rides in runs of 8 (a ring of 5
    # MiB); a ring of two columns is a run of two. A group is one packed
    # row's heads and names every block of its runs
    assert run_blocks(chip((layers, nb, bs, kv * d), jnp.bfloat16),
                      chip((layers, nb, bs, kv, dv), jnp.bfloat16),
                      n // kv, cols) == (2 if sliding else 8)
    fn = functools.partial(_paged_attention_pallas,
                           scale=1.0 / math.sqrt(d), interpret=False,
                           sliding=sliding)
    text = _assert_kernel_compiles(
        lambda q, k, v, pos, tables, q_pos, layer, sink: fn(
            q, k, v, pos, tables, q_pos, layer, None, None,
            sink=sink if sliding else None),
        chip((tokens, n, d), jnp.bfloat16),
        chip((layers, nb, bs, kv * d), jnp.bfloat16),
        chip((layers, nb, bs, kv, dv), jnp.bfloat16),
        chip((nb, bs), jnp.int32), chip((tokens, cols), jnp.int32),
        chip((tokens,), jnp.int32), chip((), jnp.int32),
        chip((n,), jnp.float32))
    assert _kernel_instruction_names(text) == {
        "swa_attention" if sliding else "paged_attention"}
    assert {"tpu.matmul", "tpu.enqueue_dma"} <= _mosaic_ops(text)
    # the pools are read where they lie: no copy of either beside them
    assert not re.findall(
        rf"bf16\[{layers},{nb},{bs},[\d,]+\]\S* (?:copy|transpose)\(", text)


def test_wide_key_window_pool_step_at_the_published_widths(chip, topo,
                                                           on_one_chip):
    """The packed step of MiMo-V2-Flash's configuration file: it compiles
    for the chip with both kernels in it, holds what the configuration
    says it holds, and hands all four pool leaves (the keys in whole
    lanes, the values by head, two head counts) back in the buffers they
    came in."""

    config, models = _cell_config("mimo-v2-flash", None)
    assert sorted(config["reduced"]) == [
        "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
        "num_hidden_layers", "vocab_size"]
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    s = config["serve"]
    slots, blocks = s["max_slots"], s["num_blocks"]
    assert cache.k.shape == (2, blocks, 128, 4 * 192)
    assert cache.v.shape == (2, blocks, 128, 4, 128)
    assert cache.wk.shape == (5, slots * 2, 128, 8 * 192)
    assert cache.wv.shape == (5, slots * 2, 128, 8, 128)
    tree = params["params"]["model"]
    sliding = tree["layers_sliding_sparse"]["layer"]
    assert sliding["attn"]["q_proj"]["kernel"].shape == (5, 4096, 12288)
    assert sliding["attn"]["k_proj"]["kernel"].shape == (5, 4096, 1536)
    assert sliding["attn"]["v_proj"]["kernel"].shape == (5, 4096, 1024)
    assert sliding["attn"]["o_proj"]["kernel"].shape == (5, 8192, 4096)
    assert sliding["attn"]["sink"].shape == (5, 64)
    assert sliding["moe"]["experts"]["down"].shape == (5, 16, 2048, 4096)
    assert sliding["moe"]["router"]["kernel"].shape == (5, 4096, 256)
    full = tree["layers_full_sparse"]["layer"]["attn"]
    assert full["k_proj"]["kernel"].shape == (1, 4096, 768)
    assert "sink" not in full
    assert params["params"]["lm_head"]["kernel"].shape == (4096, 19072)

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"paged_attention",
                                               "swa_attention"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params)) / gib
    assert 6.38 < weights < 6.40                     # 3,430M in bfloat16
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(weights - aot["weights"]) < 0.01
    assert abs(mem.temp_size_in_bytes / gib - aot["temporaries"]) < 0.05
    assert abs(held - aot["total"]) < 0.05, (held, mem)
    assert held > 0.8 * 15.75                        # the file's share

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"2,{blocks},128,768", f"2,{blocks},128,4,128",
                     f"5,{slots * 2},128,1536", f"5,{slots * 2},128,8,128")]
    assert len(stacks) == 4 and set(stacks) <= aliased, (stacks, header)

    total, differ, kernels = scope_disagreements(text)
    assert total > 0 and kernels == {"attn.kernel.full",
                                     "attn.kernel.window"}
    top = [d for d in differ if d[1].split(".")[0] != d[2].split(".")[0]]
    assert sum(d[3] for d in top) <= 0.02 * total, top


# -- the state-pool cache with held experts (Solar-Open2-250B): chip 0 of 16 ----
# The cell solar-open2-250b.serve-reasoning: 128 rows, 128 slots; 6 KDA layers
# over float32 states [6, 128, 64, 128, 128] (4 MiB a slot a layer) and
# tails [6, 3, 128, 24576], 2 gated NoPE GQA layers of 64 heads over a pool
# of 8 K/V heads of 128; 20 of 320 experts held beside a shared expert, an
# eighth of the vocabulary.

def test_kda_state_update(chip):
    """The delta rule's kernel at the published widths: a slot's state by
    tiles of eight heads, 128 rows against 128 slots."""
    from neuronx_distributed_tpu.ops import kda, ssd

    t, h, d, layers, slots = 128, 64, 128, 6, 128

    def fn(dt, kt, qt, bv, bb, state, layer, slot_ids, positions):
        seg = ssd.step_segments(slot_ids, positions, slots)
        return kda._kda_update_pallas(dt, kt, qt, bv, bb, state, layer, seg,
                                      interpret=False)

    by_column, by_row = chip((h, d, t), jnp.float32), chip((t, h * d),
                                                           jnp.float32)
    compiled = jax.jit(fn, donate_argnums=(5,)).lower(
        by_column, by_column, by_column, by_row, by_row,
        chip((layers, slots, h, d, d), jnp.float32), chip((), jnp.int32),
        chip((t,), jnp.int32), chip((t,), jnp.int32)).compile()
    assert _kernel_instruction_names(compiled.as_text()) == {
        "kda_state_update"}
    # the states are written where they lie: nothing the size of a layer's
    # beside them
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= layers * slots * h * d * d * 4
    assert mem.temp_size_in_bytes < 8 * 2 ** 20


def test_delta_rule_state_pool_step_at_the_published_widths(
        chip, topo, on_one_chip, monkeypatch):
    """The packed step of Solar-Open2-250B's configuration file: it
    compiles for the chip with both kernels in it, holds what the
    configuration says it holds (``assumed.serve_aot_gib`` is this
    analysis), leaves no stack copied, hands the pool, the states and the
    tails back in the buffers they came in, and every fusion with a
    matmul inside reads the scope of its heaviest one."""
    import re

    from neuronx_distributed_tpu.ops import kda

    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    config, models = _cell_config("solar-open2-250b", None)
    assert sorted(config["reduced"]) == [
        "gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    s = config["serve"]
    slots, blocks = s["max_slots"], s["num_blocks"]
    assert cache.k.shape == (2, blocks, 128, 8, 128) == cache.v.shape
    assert cache.states["kda"].shape == (6, slots, 64, 128, 128)
    assert cache.states["kda"].dtype == jnp.float32
    assert cache.states["conv"].shape == (6, 3, slots, 24576)
    assert cache.moe_counts.shape == (3,)
    tree = params["params"]["model"]
    mixer = tree["layers_kda"]["layer"]["attn"]
    assert mixer["qkv_proj"]["kernel"].shape == (6, 4096, 24576)
    assert mixer["low_proj"]["kernel"].shape == (6, 4096, 128 + 128 + 64)
    assert mixer["f_b_proj"]["kernel"].shape == (6, 128, 8192)
    assert mixer["g_b_proj"]["bias"].shape == (6, 8192)
    assert mixer["conv_kernel"].shape == (6, 24576, 4)
    assert mixer["A_log"].shape == (6, 64)
    assert mixer["dt_bias"].shape == (6, 8192)
    assert mixer["o_norm"]["scale"].shape == (6, 128)
    assert mixer["o_proj"]["kernel"].shape == (6, 8192, 4096)
    gqa = tree["layers_full"]["layer"]
    assert gqa["attn"]["g_proj"]["kernel"].shape == (2, 4096, 8192)
    assert gqa["attn"]["k_proj"]["kernel"].shape == (2, 4096, 1024)
    assert gqa["moe"]["router"]["kernel"].shape == (2, 4096, 320)
    assert gqa["moe"]["experts"]["down"].shape == (2, 20, 1280, 4096)
    assert gqa["moe"]["shared"]["down"]["kernel"].shape == (2, 1280, 4096)
    assert params["params"]["lm_head"]["kernel"].shape == (4096, 24576)

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"paged_attention",
                                               "kda_state_update"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params)) / gib
    assert 7.25 < weights < 7.27                     # 3,899M in bfloat16
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(weights - aot["weights"]) < 0.01
    assert abs(mem.temp_size_in_bytes / gib - aot["temporaries"]) < 0.05
    assert abs(held - aot["total"]) < 0.05, (held, mem)
    assert held > 0.8 * 15.75                        # the file's share
    assert mem.temp_size_in_bytes < slots * 64 * 128 * 128 * 4  # a layer's

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"2,{blocks},128,8,128", f"6,{slots},64,128,128",
                     f"6,3,{slots},24576")]
    assert len(stacks) == 4 and set(stacks) <= aliased, (stacks, header)

    total, differ, kernels = scope_disagreements(text)
    assert total > 0 and kernels == {"attn.kernel", "attn.state"}
    top = [d for d in differ if d[1].split(".")[0] != d[2].split(".")[0]]
    assert sum(d[3] for d in top) <= 0.02 * total, top


# -- the state-pool cache with held experts (Granite-4.0-H-Small) ----------------
# The cell granite-4.0-h-small.serve-agentic: 128 rows, 64 slots; 9 mamba
# layers of 128 heads over float32 states [9, 64, 128, 8192] and tails [9, 3,
# 64, 8448], 1 attention layer of 32 heads over 8 K/V heads of 128; after
# every layer 36 of 72 experts of 768 held beside a shared MLP of 1,536;
# half the vocabulary, tied to the head.

def test_hybrid_expert_state_pool_step_at_the_published_widths(
        chip, topo, on_one_chip, monkeypatch):
    """The packed step of Granite-4.0-H-Small's configuration file: it
    compiles for the chip with both kernels in it (the scan's state
    update at a ``[128, 8192]`` state: a 4 MiB block a slot in and out,
    sixteen tiles a row), holds what the configuration says it holds
    (``assumed.serve_aot_gib`` is this analysis), leaves no stack copied,
    hands the pool, the states and the tails back in the buffers they
    came in, and the routed experts, the router and the shared MLP have
    their scopes under both kinds of layer."""
    import re

    from neuronx_distributed_tpu.ops import ssd

    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    config, models = _cell_config("granite-4.0-h-small", None)
    assert sorted(config["reduced"]) == [
        "layer_types", "num_hidden_layers", "num_local_experts",
        "vocab_size"]
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    s = config["serve"]
    slots, blocks = s["max_slots"], s["num_blocks"]
    assert cache.k.shape == (1, blocks, 128, 8, 128) == cache.v.shape
    assert cache.states["ssm"].shape == (9, slots, 128, 8192)
    assert cache.states["ssm"].dtype == jnp.float32
    assert cache.states["conv"].shape == (9, 3, slots, 8448)
    assert cache.moe_counts.shape == (3,)
    tree = params["params"]["model"]
    mamba = tree["layers_mamba2"]["layer"]
    assert mamba["attn"]["in_proj"]["kernel"].shape == (9, 4096, 16768)
    assert mamba["attn"]["conv_kernel"].shape == (9, 8448, 4)
    assert mamba["attn"]["A_log"].shape == (9, 128)
    assert mamba["moe"]["router"]["kernel"].shape == (9, 4096, 72)
    assert mamba["moe"]["experts"]["gate"].shape == (9, 36, 4096, 768)
    assert mamba["moe"]["experts"]["down"].shape == (9, 36, 768, 4096)
    assert mamba["moe"]["shared"]["down"]["kernel"].shape == (9, 1536, 4096)
    full = tree["layers_full"]["layer"]
    assert full["attn"]["qkv"]["k_kernel"].shape == (1, 4096, 1024)
    assert full["moe"]["experts"]["up"].shape == (1, 36, 4096, 768)
    assert tree["embed"]["embedding"].shape == (50176, 4096)
    assert "lm_head" not in params["params"]

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"paged_attention",
                                               "ssd_state_update"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(x.size for x in leaves) == 4_757_211_776
    weights = sum(x.size * x.dtype.itemsize for x in leaves)  # 8.861 GiB
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(weights / gib - aot["weights"]) < 0.01
    assert abs(mem.temp_size_in_bytes / gib - aot["temporaries"]) < 0.05
    assert abs(held - aot["total"]) < 0.05, (held, mem)
    assert held >= 0.85 * 15.75                      # the file's share
    assert mem.temp_size_in_bytes < slots * 128 * 8192 * 4    # a layer's

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"1,{blocks},128,8,128", f"9,{slots},128,8192",
                     f"9,3,{slots},8448")]
    assert len(stacks) == 4 and set(stacks) <= aliased, (stacks, header)

    total, differ, kernels = scope_disagreements(text)
    assert total > 0 and kernels == {"attn.kernel", "attn.state"}
    top = [d for d in differ if d[1].split(".")[0] != d[2].split(".")[0]]
    assert sum(d[3] for d in top) <= 0.02 * total, top
    for scope in ("ffn.router", "ffn.experts", "ffn.shared"):
        assert f"nxd.{scope}" in text, scope


# The cell nemotron-3-super.serve-reasoning: 128 rows, 128 slots; five
# Mamba-2 layers of 128 heads in 8 groups over float32 states [5, 128, 128,
# 8192] and tails [5, 3, 128, 10240], one attention layer of 32 heads over 2
# K/V heads of 128; five LatentMoE layers: 128 of 512 squared-ReLU experts
# of 2,688 in a latent of 1,024 beside a shared expert of 5,376; a quarter
# of the vocabulary, the head untied.

def test_nemotron_state_pool_step_at_the_published_widths(
        chip, topo, on_one_chip, monkeypatch):
    """The packed step of Nemotron-3-Super's configuration file: it
    compiles for the chip with both kernels in it (the scan's state
    update at a ``[128, 8192]`` state whose sixteen tiles a row read
    ``B`` and ``C`` of eight groups), holds what the configuration says
    it holds (``assumed.serve_aot_gib`` is this analysis), hands the
    pool, the states and the tails back in the buffers they came in, and
    the router, the latent pair, the bank and the shared expert have
    their scopes."""
    import re

    from neuronx_distributed_tpu.ops import ssd

    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    config, models = _cell_config("nemotron-3-super-120b-a12b", None)
    assert sorted(config["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts",
        "num_hidden_layers", "num_nextn_predict_layers", "vocab_size"]
    cfg, forward, params, cache, tokens = _serving_parts(chip, config,
                                                         models)
    s = config["serve"]
    slots, blocks = s["max_slots"], s["num_blocks"]
    assert (slots, blocks, tokens) == (128, 12288, 128)
    assert cache.k.shape == (1, blocks, 128, 2, 128) == cache.v.shape
    assert cache.states["ssm"].shape == (5, slots, 128, 8192)
    assert cache.states["ssm"].dtype == jnp.float32
    assert cache.states["conv"].shape == (5, 3, slots, 10240)
    assert cache.moe_counts.shape == (5,)
    assert cfg.runs() == (("mamba2_moe", 0, 3), ("mamba2", 0, 1),
                          ("full_moe", 0, 1), ("mamba2_moe", 3, 1))
    tree = params["params"]["model"]
    paired = tree["layers_mamba2_moe"]["layer"]
    assert paired["attn"]["in_proj"]["kernel"].shape == (4, 4096, 18560)
    assert paired["attn"]["conv_kernel"].shape == (4, 10240, 4)
    assert paired["attn"]["norm"]["scale"].shape == (4, 8192)
    assert paired["moe"]["router"]["kernel"].shape == (4, 4096, 512)
    assert paired["moe"]["router"]["bias"].shape == (4, 512)
    assert paired["moe"]["latent_in"].shape == (4, 4096, 1024)
    assert paired["moe"]["latent_out"].shape == (4, 1024, 4096)
    assert paired["moe"]["experts"]["up"].shape == (4, 128, 1024, 2688)
    assert paired["moe"]["experts"]["down"].shape == (4, 128, 2688, 1024)
    assert "gate" not in paired["moe"]["experts"]
    assert paired["moe"]["shared"]["up_kernel"].shape == (4, 4096, 5376)
    assert set(tree["layers_mamba2"]["layer"]) == {"attn", "input_norm"}
    full = tree["layers_full_moe"]["layer"]
    assert full["attn"]["qkv"]["k_kernel"].shape == (1, 4096, 256)
    assert full["moe"]["experts"]["up"].shape == (1, 128, 1024, 2688)
    assert tree["embed"]["embedding"].shape == (32768, 4096)
    assert params["params"]["lm_head"]["kernel"].shape == (4096, 32768)

    compiled = _packed_step(chip, cfg, forward, params, cache, tokens)
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"paged_attention",
                                               "ssd_state_update"}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(x.size for x in leaves) == 4_648_163_712
    weights = sum(x.size * x.dtype.itemsize for x in leaves)  # 8.658 GiB
    aot = config["assumed"]["serve_aot_gib"]
    assert abs(weights / gib - aot["weights"]) < 0.01
    assert abs(mem.temp_size_in_bytes / gib - aot["temporaries"]) < 0.05
    assert abs(held - aot["total"]) < 0.05, (held, mem)
    assert held >= 0.80 * 15.75                      # the file's share
    assert mem.temp_size_in_bytes < slots * 128 * 8192 * 4    # a layer's

    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape in (f"1,{blocks},128,2,128", f"5,{slots},128,8192",
                     f"5,3,{slots},10240")]
    assert len(stacks) == 4 and set(stacks) <= aliased, (stacks, header)

    total, differ, kernels = scope_disagreements(text)
    assert total > 0 and kernels == {"attn.kernel", "attn.state"}
    top = [d for d in differ if d[1].split(".")[0] != d[2].split(".")[0]]
    assert sum(d[3] for d in top) <= 0.02 * total, top
    for scope in ("ffn.router", "ffn.latent", "ffn.experts", "ffn.shared",
                  "attn.state", "attn.conv", "attn.proj"):
        assert f"nxd.{scope}" in text, scope


# -- the engine's own packed step: one deep in flight -------------------------
# The CPU tests never donate, so only a compile for the chip shows what the
# step's operands are there: the pool donated and written in place, the
# tables, lengths and device-side counts held out of the donation (the host
# keeps those arrays while the next step runs), no table handed back.

@pytest.mark.parametrize("config_name, layers", [
    ("mistral-7b-serve", 2), ("evabyte-6.5b", 2),
    ("minicpm-sala-9b", None), ("glm-4.7-flash", None),
    ("longcat-flash-chat", None)])
def test_the_engines_step_donates_its_pool_and_not_the_hosts_leaves(
        chip, on_one_chip, monkeypatch, config_name, layers):
    import re
    import types

    from neuronx_distributed_tpu.inference import engine as eng
    from neuronx_distributed_tpu.inference.sampling import SamplingConfig

    monkeypatch.setattr(eng, "on_tpu", lambda: True)
    config, models = _cell_config(config_name, layers)
    cfg, forward, params, cache, width = _serving_parts(chip, config,
                                                        models)
    step = eng.ServingEngine._build_step(types.SimpleNamespace(
        model_cfg=cfg, _forward_fn=forward, _cp=1, _spec=None, _block=None,
        ecfg=types.SimpleNamespace(sampling=SamplingConfig(greedy=True))))
    pool, held = eng._hold_out(cache)
    assert set(held) >= {"block_tables", "lengths"}
    row = chip((width,), jnp.int32)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    compiled = step.lower(
        params, pool, held, chip((1, width), jnp.int32),
        chip((1, width), jnp.int32), row, row, row,
        chip(rng.shape, rng.dtype)).compile()
    text = compiled.as_text()
    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    shape_of = {int(n): shape for shape, n in re.findall(
        r" = \w+\[([\d,]*)\]\S* parameter\((\d+)\)", entry)}
    aliased = {shape_of[int(n)] for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}

    def dims(x):
        return ",".join(map(str, x.shape))

    # every leaf of the pool is handed back in the buffer it came in ...
    large = {dims(x) for x in jax.tree_util.tree_leaves(pool)}
    assert large and large <= aliased, (large, aliased)
    # ... and nothing the host keeps is: not the tables, not the counts
    kept = {dims(x) for x in held.values()}
    assert not kept & aliased
    results = re.search(r"->\s*\((.*?)\)\}", header).group(1)
    assert f"s32[{dims(held['block_tables'])}]" not in results
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool))
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < pool_bytes / 4
    if config_name == "glm-4.7-flash":
        # the cell's bytes (PERF.md section 4: 12.817 / 0.012 GiB): the
        # latent kernel's ring of blocks is VMEM and its walk kilobytes
        gib = 2.0 ** 30
        assert round(memory.argument_size_in_bytes / gib, 3) == 12.817
        assert memory.temp_size_in_bytes / gib < 0.0125
        assert _kernel_instruction_names(text) == {"mla_paged_attention"}


# -- the block family's packed step (models/sdar.py) at the published widths:
# the step of inference/block_serving.py, as the engine builds it

def test_block_step_at_the_published_widths(chip, topo, on_one_chip,
                                            monkeypatch):
    """The packed step of ``sdar-30b-a3b-chat``: forward, the draw with
    its confidence, the uncover rule and the device-held block state. It
    compiles for the chip with the paged kernel and the grouped product
    in it, holds the arguments the configuration's
    ``assumed.serve_aot_gib`` says, writes the pool in place, and copies
    no layer's bank: the grouped kernel is a custom call that takes the
    banks' stacks and the layer's index (``ops/blockwise_moe.py``, "The
    layers' stacks"; until PR 68 the scan's slices were written out in
    front of it, 1.232 GiB of temporaries and 21.7 ms of a 58 ms step).
    What the configuration file says of ``temporaries`` and ``peak``
    describes that copy (PERF.md section 7, a debt of the harness)."""
    import re
    import types

    from neuronx_distributed_tpu.inference import block_serving, engine
    from neuronx_distributed_tpu.obs.device_scopes import scope_of
    from neuronx_distributed_tpu.ops import blockwise_moe
    from neuronx_distributed_tpu.ops import paged_attention as pa

    monkeypatch.setattr(block_serving, "on_tpu", lambda: True)
    monkeypatch.setattr(blockwise_moe, "on_tpu", lambda: True)
    # the paged kernel as the step builds it: its group is a slot's four
    # rows' heads, 32 of the tile's 128 stacked rows (PR 70)
    groups = []
    run_kernel = pa._paged_run_kernel

    def spy(*refs, group, whole_named, **kw):
        groups.append((group, whole_named, kw["run"]))
        return run_kernel(*refs, group=group, whole_named=whole_named, **kw)

    monkeypatch.setattr(pa, "_paged_run_kernel", spy)
    config, models = _cell_config("sdar-30b-a3b-chat", None)
    assert set(config["reduced"]) == {"num_hidden_layers"}
    cfg, forward, params, cache, width = _serving_parts(chip, config, models)
    s, layers = config["serve"], config["num_hidden_layers"]
    nb, bs = s["num_blocks"], s["block_size"]
    assert cache.k.shape == cache.v.shape == (layers, nb, bs, 4, 128)
    assert cache.moe_counts.shape == (2,) and cache.states == {}
    bank = params["params"]["model"]["layers"]["layer"]["moe"]["experts"]
    assert bank["gate"].shape == bank["up"].shape == (layers, 128, 2048, 768)
    assert bank["down"].shape == (layers, 128, 768, 2048)
    attn = params["params"]["model"]["layers"]["layer"]["attn"]
    assert attn["o_proj"]["kernel"].shape == (layers, 4096, 2048)
    assert attn["q_norm"]["scale"].shape == (layers, 128)
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == 4_361_055_744

    ecfg = engine.EngineConfig(
        block_size=bs, num_blocks=nb, max_slots=s["max_slots"],
        max_blocks_per_seq=s["max_blocks_per_seq"], token_budget=width,
        kv_dtype=cfg.dtype)
    step = block_serving.BlockServing._build_block_step(types.SimpleNamespace(
        model_cfg=cfg, ecfg=ecfg, _forward_fn=forward,
        _block=cfg.block_decoding))
    b, slots = cfg.block_decoding.block_length, s["max_slots"] + 1
    state = dict(start=chip((slots,), jnp.int32),
                 tok=chip((slots, b), jnp.int32),
                 masked=chip((slots, b), jnp.bool_),
                 npass=chip((slots,), jnp.int32),
                 done=chip((slots,), jnp.bool_))
    pool, held = engine._hold_out(cache)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    compiled = step.lower(
        params, pool, held, chip((1, width), jnp.int32),
        chip((1, width), jnp.int32), chip((width,), jnp.int32), state,
        chip((3, width // b), jnp.int32), chip(rng.shape, rng.dtype)
    ).compile()
    text = compiled.as_text()
    assert _kernel_instruction_names(text) == {"paged_attention",
                                               "grouped_glu_fwd"}
    assert set(groups) == {(32, False, 8)}
    gib = 2.0 ** 30
    mem = compiled.memory_analysis()
    arguments = config["assumed"]["serve_aot_gib"]["arguments"]
    assert abs(mem.argument_size_in_bytes / gib - arguments) < 0.01
    # what is left beside the arguments is the head's float32 logits
    # (640 rows of 151,936, 0.362 GiB, which the bank's copy used to
    # cover) and little else: less than one leaf of a bank
    logits = width * cfg.vocab_size * 4
    one_leaf = 128 * 2048 * 768 * 2
    assert logits <= mem.temp_size_in_bytes < min(one_leaf,
                                                  logits + 0.05 * gib)
    assert 0 <= (mem.peak_memory_in_bytes
                 - mem.argument_size_in_bytes) < logits + 0.05 * gib
    assert 0.60 <= mem.peak_memory_in_bytes / gib / 15.75 <= 0.90
    # no buffer of a bank's shape but the stacks' own, and no slice of a
    # stack in front of the custom call: its three weight operands are the
    # scan's own stacks
    assert _bank_shaped(text, 128, 2048, 768) is None
    call = re.search(r" custom-call\(([^)]*)\), custom_call_target="
                     r'"tpu_custom_call"[^\n]*grouped_glu_fwd', text)
    defined = dict(re.findall(r"\n\s+(?:ROOT )?(%[\w.-]+) = (\S+) ", text))
    operands = [defined[name] for name in re.findall(r"%[\w.-]+",
                                                     call.group(1))]
    assert sum(shape.startswith(f"bf16[{layers},128,2048,768]")
               for shape in operands) == 2, operands
    assert sum(shape.startswith(f"bf16[{layers},128,768,2048]")
               for shape in operands) == 1, operands
    header, entry = text.split("\n", 1)[0], text.split("\nENTRY ", 1)[1]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    stacks = [int(n) for shape, n in re.findall(
        r" = \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)
        if shape == f"{layers},{nb},{bs},4,128"]
    assert len(stacks) == 2 and set(stacks) <= aliased, (stacks, header)
    seen = {scope_of(m) for m in re.findall(r'op_name="([^"]*)"', text)}
    assert {"sample", "sample.uncover", "ffn.experts", "ffn.router",
            "attn.kernel", "attn.pool_write", "attn.walk"} <= seen
