"""Device scopes (``obs/device_scopes.py``): what ``scope_of`` reads from a
path, and that the program's step functions open the scopes where the
taxonomy says, on every operation that carries a step's weight, without
changing an operation.

The steps are the benchmark's rehearsal configurations
(``benchmarks/tests/configs``) through ``ServingEngine``'s own packed
step and ``make_train_step``, lowered on the CPU and never run.
"""

import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from flax.core import meta

from neuronx_distributed_tpu.obs import device_scopes as ds
from neuronx_distributed_tpu.obs.device_scopes import (SCOPES, UNSCOPED,
                                                       device_scope,
                                                       scope_of, within)
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
from runners import models  # noqa: E402

SERVING = {"llama": "tiny-mistral-serve", "mixtral": "tiny-mixtral",
           "evabyte": "tiny-evabyte", "minicpm_sala": "tiny-minicpm-sala",
           "glm_moe_lite": "tiny-glm-moe-lite",
           "granite_hybrid": "tiny-granite-hybrid",
           "laguna": "tiny-laguna",
           "mimo_v2_flash": "tiny-mimo-v2-flash",
           "solar_open2": "tiny-solar-open2",
           "longcat_flash": "tiny-longcat-flash",
           "granite_moe_hybrid": "tiny-granite-moe-hybrid",
           "nemotron_h": "tiny-nemotron-h",
           "xing4": "tiny-xing4", "sdar_moe": "tiny-sdar",
           "deepseek_v32": "tiny-deepseek-v32"}
#: the children a family's step must open, and no other family's may
OWN = {"attn.select": {"minicpm_sala", "deepseek_v32"},
       # the index scores of a family whose rows select single positions
       "attn.index": {"deepseek_v32"},
       "attn.state": {"minicpm_sala", "granite_hybrid", "solar_open2",
                      "granite_moe_hybrid", "nemotron_h"},
       "attn.conv": {"granite_hybrid", "solar_open2", "granite_moe_hybrid",
                     "nemotron_h"},
       "attn.summarise": {"evabyte"},
       "attn.kernel.full": {"laguna", "mimo_v2_flash"},
       "attn.kernel.window": {"laguna", "mimo_v2_flash"},
       "ffn.experts": {"mixtral", "glm_moe_lite", "laguna", "mimo_v2_flash",
                       "solar_open2", "longcat_flash", "granite_moe_hybrid",
                       "nemotron_h", "xing4", "sdar_moe", "deepseek_v32"},
       "ffn.router": {"mixtral", "glm_moe_lite", "laguna", "mimo_v2_flash",
                      "solar_open2", "longcat_flash", "granite_moe_hybrid",
                      "nemotron_h", "xing4", "sdar_moe", "deepseek_v32"},
       "ffn.shared": {"glm_moe_lite", "laguna", "solar_open2",
                      "granite_moe_hybrid", "nemotron_h", "xing4",
                      "deepseek_v32"},
       "ffn.latent": {"nemotron_h"},
       # the float32 product with Phi; hc.apply has no heavy operation
       "hc.mix": {"xing4"},
       # a block family's rule: its top_k and the state's scatters
       "sample.uncover": {"sdar_moe"}}
#: the operations that carry a step's device time
HEAVY = ("stablehlo.dot_general", "stablehlo.custom_call",
         "stablehlo.scatter", "stablehlo.gather", "stablehlo.sort",
         "chlo.top_k", "stablehlo.convolution")


# -- scope_of -----------------------------------------------------------------

@pytest.mark.parametrize("path,want", [
    ("jit(step_fn)/nxd.sample/argmax", "sample"),
    ("jit(step_fn)/while/body/closed_call/_PagedScanBody/layer/nxd.attn/"
     "attn/nxd.attn.kernel/nxd.attn.pool_write/scatter", "attn.pool_write"),
    ("jit(step_fn)/while/body/closed_call/LlamaDecoderLayer/nxd.ffn/moe/"
     "nxd.ffn.experts/routed_experts/experts/dot_general", "ffn.experts"),
    ("jit(step)/transpose(jvp(nxd.loss))/add_any", "loss"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/nxd.attn/nxd.attn.proj/dot_general", "attn.proj"),
    ("jit(step)/jvp()/while/body/closed_call/nxd.ffn/add", "ffn"),
    ("jit(step_fn)/while/body/closed_call/_PagedScanBody/layer/attn/qkv/"
     "dot_general", UNSCOPED),
    ("scatter", UNSCOPED),
    ("", UNSCOPED),
    (None, UNSCOPED),
    # a module that happens to be called like a marker's stem is no marker
    ("jit(f)/attn.kernel/dot_general", UNSCOPED),
])
def test_scope_of_reads_the_innermost_marker(path, want):
    assert scope_of(path) == want


def test_device_scope_refuses_a_name_outside_the_tuple():
    with pytest.raises(ValueError, match="no device scope"):
        device_scope("attention")
    for name in SCOPES:
        assert scope_of(f"jit(f)/{ds.PREFIX}{name}/mul") == name
        stem = name.split(".")[0]
        assert stem in SCOPES and within(name, [stem])
    assert not within("attn", ["attn.kernel"])
    assert not within("attnx", ["attn"])


def test_a_scope_survives_jit_scan_remat_and_differentiation():
    def layer(x, w):
        with device_scope("attn"):
            with device_scope("attn.proj"):
                y = x @ w
            return x + jnp.tanh(y)

    def loss(x, ws):
        x, _ = jax.lax.scan(
            lambda c, w: (jax.checkpoint(layer)(c, w), None), x, ws)
        with device_scope("loss"):
            return jnp.sum(x * x)

    text = jax.jit(jax.grad(loss, argnums=1)).lower(
        jnp.ones((8, 8)), jnp.ones((3, 8, 8))).compile().as_text()
    seen = {}
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and " dot(" in line:
            seen.setdefault(scope_of(m.group(1)), []).append(m.group(1))
    assert set(seen) == {"attn.proj"}
    assert any("rematted_computation" in p for p in seen["attn.proj"])
    assert any("transpose(jvp" in p for p in seen["attn.proj"])


# -- the lowered steps ---------------------------------------------------------

def _paths(text):
    """``{#locN: path}`` of a module printed with debug info: the name an
    operation was traced under, ``#loc7 = loc("jit(f)/a/mul"(#loc3))``."""
    return dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"\(#loc\d+\)\)$',
                           text, re.M))


def _operations(lowered):
    """``[(operation, path)]`` of every operation of the lowered module,
    as the compiled program will name it: inside a private function an
    operation's path is relative, and continues that of the ``call`` that
    reaches it (``.../while/body/closed_call`` + ``/nxd.attn/add``)."""
    text = lowered.as_text(debug_info=True)
    named = _paths(text)
    funcs, current, open_ops, pending = {}, None, [], None
    name = re.compile(r'"?((?:stablehlo|chlo|func)\.[a-z_]+|call)"?[ (]')
    for line in text.splitlines():
        line = line.strip()
        head = re.match(r"func\.func (?:public |private )?@([\w.]+)\(", line)
        if head:
            current = funcs.setdefault(head.group(1), [])
        loc = re.search(r"loc\((#loc\d+)\)$", line)
        op = None if head else name.search(line)
        if line.startswith("}"):
            # a region ends: of an operation with a body (a scatter, a
            # sort, a while), whose location follows its last, or of a
            # function; "} do {" goes on to the operation's next
            if not line.endswith("{"):
                ended = open_ops.pop()
                if ended and loc:
                    current.append((ended, named.get(loc.group(1), ""),
                                    None))
        elif line.endswith("{"):
            open_ops.append(op.group(1) if op else pending)
            pending = None
        elif op and loc:
            callee = re.search(r"call @([\w.]+)\(", line)
            current.append((op.group(1), named.get(loc.group(1), ""),
                            callee and callee.group(1)))
        elif op:
            pending = op.group(1)       # its regions follow
    assert not open_ops, open_ops
    out = []

    def walk(name, prefix):
        for op, path, callee in funcs[name]:
            full = f"{prefix}/{path}" if prefix and path else prefix or path
            if callee:
                walk(callee, full)
            else:
                out.append((op, full))

    walk("main", "")
    return out


def _heavy_ops(lowered):
    return [(op, path) for op, path in _operations(lowered) if op in HEAVY]


def _stripped(lowered):
    """The lowered module without its locations: the operations alone."""
    return lowered.as_text(debug_info=False)


def _serving_step(family):
    from neuronx_distributed_tpu.inference.engine import ServingEngine
    from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION

    config = harness.read_json(os.path.join(
        BENCH, "tests", "configs", SERVING[family] + ".json"))
    settings = config["serve"]
    from runners import serve

    dtype = models.dtype_of(settings["dtype"])
    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    mcfg, model, _ = models.build(config, dtype=dtype, param_dtype=dtype,
                                  **settings.get("model", {}))
    params = meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), params)
    ecfg = serve._engine_config(settings, dtype)
    engine = ServingEngine(mcfg, params, ecfg)
    return engine._build_step().lower(
        *engine._example_args(ecfg.token_budget))


def _train_step():
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, initialize_parallel_optimizer,
        make_train_step)

    config = harness.read_json(os.path.join(
        BENCH, "tests", "configs", "tiny-mistral.json"))
    settings = config["train"]
    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=settings["tensor_parallel_size"],
        optimizer_config=nxd.OptimizerConfig(
            zero_one_enabled=settings["zero1"]),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode=settings["activation_checkpoint"]),
        sequence_parallel=settings["sequence_parallel"])
    base, module, _ = models.build(
        config, max_seq_len=64,
        dtype=models.dtype_of(settings["compute_dtype"]),
        param_dtype=models.dtype_of(settings["param_dtype"]),
        use_flash_attention=settings["flash_attention"])
    mcfg = nxd.configure_model(cfg, base)
    model = type(module)(mcfg)
    batch = {"input_ids": jnp.zeros((2, 64), jnp.int32),
             "labels": jnp.zeros((2, 64), jnp.int32)}
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(0), batch["input_ids"])
    tx, state, shardings = initialize_parallel_optimizer(
        pm, params, learning_rate=1e-4)
    return make_train_step(pm, tx, shardings).lower(state, batch)


@functools.lru_cache(maxsize=None)
def _lowered(which):
    try:
        return _train_step() if which == "train" else _serving_step(which)
    finally:
        ps.destroy_model_parallel()


@pytest.mark.parametrize("which", list(SERVING) + ["train"])
def test_every_heavy_operation_of_a_step_has_a_scope(which):
    ops = _heavy_ops(_lowered(which))
    assert len(ops) > 10
    assert any(op == "stablehlo.dot_general" for op, _ in ops)
    bare = [(op, path) for op, path in ops if scope_of(path) == UNSCOPED]
    assert bare == []


@pytest.mark.parametrize("which", list(SERVING) + ["train"])
def test_a_step_opens_the_children_its_family_has_and_no_others(which):
    seen = {scope_of(path) for _, path in _heavy_ops(_lowered(which))}
    assert seen <= set(SCOPES)
    assert {"attn.proj", "head"} <= seen
    # the kernel's scope, or its children where a family's layers differ
    assert any(within(name, ["attn.kernel"]) for name in seen)
    for child, families in OWN.items():
        assert (child in seen) == (which in families), (child, seen)
    dense = which in ("llama", "evabyte", "minicpm_sala", "glm_moe_lite",
                      "granite_hybrid", "laguna", "mimo_v2_flash",
                      "longcat_flash", "xing4", "deepseek_v32", "train")
    assert ("ffn.dense" in seen) == dense
    if which == "train":
        assert "optimizer" not in seen      # elementwise: no heavy operation
        assert "sample" not in seen
    else:
        assert {"attn.pool_write", "attn.walk"} <= seen


@pytest.mark.parametrize("which", list(SERVING))
def test_a_serving_step_samples_under_its_own_scope(which):
    paths = {path for _, path in _operations(_lowered(which))}
    assert any(scope_of(p) == "sample" for p in paths)
    assert not any(scope_of(p) in ("loss", "optimizer") for p in paths)


@pytest.mark.parametrize("which", list(SERVING))
def test_the_identity_experts_term_has_a_scope_of_its_own(which):
    """No heavy operation (a sum of a row's identity weights and a scaled
    add), so the children's table above cannot see it: the step of the
    one family that has identity experts opens ``ffn.identity``."""
    scopes = {scope_of(path) for _, path in _operations(_lowered(which))}
    assert ("ffn.identity" in scopes) == (which == "longcat_flash")


@pytest.mark.parametrize("which", list(SERVING))
def test_the_streams_mixing_has_scopes_of_its_own(which):
    """The one family whose residual is several streams opens ``hc.mix``
    (the statistic, the product with ``Phi``, the sigmoids, the Sinkhorn
    rounds) and ``hc.apply`` (the read, the write, the widening and the
    sum at the ends; no heavy operation, so the children's table above
    cannot see it), each twice a layer; no other step opens either, and
    in this one the unrolled rounds are elementwise operations alone: no
    reduction is traced under ``hc.mix`` but the statistic's."""
    ops = _operations(_lowered(which))
    scopes = {scope_of(path) for _, path in ops}
    assert ({"hc.mix", "hc.apply"} <= scopes) == (which == "xing4")
    assert not within("hc", scopes) or which == "xing4"
    if which == "xing4":
        mix = [op for op, path in ops if scope_of(path) == "hc.mix"]
        # two kinds of layer, two sublayers each: one mean of squares and
        # one product a sublayer
        assert mix.count("stablehlo.reduce") == 4
        assert mix.count("stablehlo.dot_general") == 4
        assert mix.count("stablehlo.divide") >= 4 * 40 * 16
        assert "stablehlo.while" not in mix


def test_the_train_step_marks_its_loss_and_its_optimizer():
    paths = {path for _, path in _operations(_lowered("train"))}
    scopes = {scope_of(p) for p in paths}
    assert {"loss", "optimizer", "attn.kernel", "ffn.dense", "norm",
            "embed", "head"} <= scopes
    assert any("rematted_computation" in p and scope_of(p) == "ffn.dense"
               for p in paths)


@pytest.mark.parametrize("which", list(SERVING) + ["train"])
def test_the_markers_change_no_operation(which, monkeypatch):
    """Lowered with ``jax.named_scope`` a null context, the step is the
    same text once locations are stripped: a marker is metadata."""
    marked = _stripped(_lowered(which))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        bare_lowered = (_train_step() if which == "train"
                        else _serving_step(which))
    finally:
        ps.destroy_model_parallel()
    assert not any(scope_of(path) != UNSCOPED
                   for _, path in _operations(bare_lowered))
    assert _stripped(bare_lowered) == marked


#: sha256 of a family's lowered packed step at the rehearsal widths,
#: locations stripped, as PR 38's tree lowered it (``_stripped(_lowered(
#: family))`` there): the families that run no ``ops/mla_attention.py``
LOWERED_AT_PR_38 = {
    "llama":
        "26e1d5185b42b1a3ebb9e2ce736f5e85f46ef1bae8c338549a38499737339aa4",
    "mixtral":
        "980493c6574fdd048c8fc81af4a42da9f8e4f1d8cdc346900a88ff1592f19bcf",
    "evabyte":
        "c947e1b8bab1fdb356b3c957e80421564c15e960e94a849b27e073c8bb9fdc98",
    "granite_hybrid":
        "986d4a577868df39b211138f57cc1cb0a1f2880925b82928852429e39db15783",
}


#: and the latent family's, as PR 39's tree lowered it
LOWERED_AT_PR_39 = {
    "glm_moe_lite":
        "63c8641022c750d872b9e8ea66180b6757a26e9d639a71b44108bf4b6fcc44df",
}


#: and the sparse-state family's, as PR 42's tree lowers it (the step's
#: walk of the compressed keys, two more counts)
LOWERED_AT_PR_42 = {
    "minicpm_sala":
        "a961d5bb332c7f169899be79a0ba2bea38c677e4ff03895bf3a945a194710585",
}


#: and the window-pool family's, as PR 43's tree lowers it (recorded by
#: PR 44 before it moved the step's counters behind the cache kinds)
LOWERED_AT_PR_43 = {
    "laguna":
        "950d46e755521f7e1b0cb917d6c7c2829d5a01561f4ad1788529531d64116000",
}


#: and the second window-pool family's, as PR 45's tree lowers it (keys
#: wider than the values, a sink operand): new with that PR
LOWERED_AT_PR_45 = {
    "mimo_v2_flash":
        "c21da2185cc28ad082a28d43b35445c4a3da6500e538fad6cedfdbfdd7a4acbd",
}


#: and the state-pool family with held experts, as PR 48's tree lowers it
#: (the delta rule's packed step, ``moe_counts`` beside the states): new
#: with that PR
LOWERED_AT_PR_48 = {
    "solar_open2":
        "15a36b8ea18473ccbfa4c375e43d71216b895561dcaf3ad429434cb1b8702195",
}


#: and the second latent family's (the shortcut-connected double layer
#: over two layers of rows), as PR 54's tree lowers it, which is how PR
#: 53's did: the one family that had no recorded text
LOWERED_AT_PR_54 = {
    "longcat_flash":
        "ea14780e48fd92dc155782f7b3801ed414c66a3400b83e42d8267d6457fd76bd",
}


#: the state-pool family with routed experts after both kinds of layer,
#: as PR 58's tree lowers it (recorded there: it had no recorded text),
#: and the family of one-block layers, new with PR 59, as that PR's tree
#: lowers it
LOWERED_AT_PR_59 = {
    "granite_moe_hybrid":
        "fd01532d7c67207eb447052b85fbdba2b9a807b6e8fb018363667dbe8ec9e201",
    "nemotron_h":
        "0d3d20b16ed2bed8375fa7567eed7d78f5a536e3bb78c56df27e19a3a269ddf9",
}


#: the family of several residual streams (``models/xing4.py``: GLM's
#: attention, experts and served forward behind the carry's, the score's
#: and the rotary rows' hooks), new with PR 63, as that PR's tree lowers it
LOWERED_AT_PR_63 = {
    "xing4":
        "277888add9250afbe8c96c65ce4c4f60000af0365341d7e020638b4d831914b2",
}


#: the family that decodes blocks (``models/sdar.py``), as PR 68's tree
#: lowers it: the one step that PR changed (off the chip the grouped
#: product's reference indexes the banks' stacks at the layer, where the
#: parent's tree sliced every leaf), and it had no recorded text
LOWERED_AT_PR_68 = {
    "sdar_moe":
        "6e76b244972957f850e0f9e3058f2b21164eaab5694d835093a77b5d086604de",
}


@pytest.mark.parametrize("which", list(LOWERED_AT_PR_38)
                         + list(LOWERED_AT_PR_39) + list(LOWERED_AT_PR_42)
                         + list(LOWERED_AT_PR_43) + list(LOWERED_AT_PR_45)
                         + list(LOWERED_AT_PR_48) + list(LOWERED_AT_PR_54)
                         + list(LOWERED_AT_PR_59) + list(LOWERED_AT_PR_63)
                         + list(LOWERED_AT_PR_68))
def test_a_family_off_the_latent_kernel_lowers_to_its_recorded_text(which):
    """PR 39 changed the latent kernel and its walk alone: the packed
    steps of the five families that run the shared helpers of
    ``ops/paged_attention.py`` are the parent's text. PR 40 gave the
    paged kernel a sliding window, the expert layer a share of the
    experts (``held``) and the top-k router a scale, each off where a
    family does not ask for it: the six families' steps, Mixtral's and
    GLM's expert layers among them, are still the text they were. PR 42
    changed the sparse-state family's step (``ops/sparse_attention.py``:
    the selection's scores, their walk and its counts) and no other.
    PR 44 moved what the host counts of a step behind the cache kinds
    and changed no program: all seven are the text they were. PR 45 gave
    the paged kernel keys wider than the values and a sink operand, each
    off where a family does not ask for it, and moved the window-pool
    family's pattern and forward to ``models/window_pool.py``: the seven
    are still the text they were, and the new family's is recorded.
    PR 48 gave the state-pool kind a ``moe_counts`` leaf, built only where
    a family declares it, and a counter of held bytes that the host
    counts: Granite's step, Laguna's and MiMo's are the text they were,
    and the delta-rule family's is recorded. PR 54 changed the latent
    kernel's shared unit (``ops/mla_attention.py``: slabs of a tall tile,
    a unit's blocks from the slab's scores) and a docstring of
    ``ops/paged_attention.py``: the nine others are the text they were,
    GLM's among them (its tile is one slab), and the second latent
    family's step, the one without a recorded text, is recorded as that
    tree and its parent lower it (off the chip a latent step gathers:
    the kernel's own text is ``tests/test_chip_compile.py``'s). PR 59
    gave the state-space scan groups of ``B`` and ``C`` and its gated
    norm groups, the expert bank and the shared expert an ungated form,
    ``MoE`` a latent pair and a count of the held experts a step hits,
    and the decoder layer blocks that may be absent, each off where a
    family does not ask for it: the ten are the text they were, both
    Granite steps (the dense one, and the one with routed experts as its
    parent's tree lowers it, recorded with that PR) and GLM's and
    Laguna's gated banks among them. PR 63 gave the latent family's
    config three hooks (the carry's way in and out, identity by default;
    the score's scale and the rotary rows as the config's, GLM's and
    LongCat's what they were) and the latent cache kind a host counter:
    the twelve are the text they were, and the family of several
    residual streams is recorded. PR 68 made ``run_layers`` hand every
    layer its leaves' stacks and its index beside the slices, in a second
    collection that only the blockwise expert bank reads
    (``modules/layer_stack.py``): the thirteen are the text they were,
    the ten whose layers run under ``run_layers`` among them, and the
    block family's step, the one it changed, is recorded.
    A PR that changes one of these programs on purpose records its new
    hash here."""
    import hashlib

    text = _stripped(_lowered(which))
    assert hashlib.sha256(text.encode()).hexdigest() == {
        **LOWERED_AT_PR_38, **LOWERED_AT_PR_39, **LOWERED_AT_PR_42,
        **LOWERED_AT_PR_43, **LOWERED_AT_PR_45, **LOWERED_AT_PR_48,
        **LOWERED_AT_PR_54, **LOWERED_AT_PR_59, **LOWERED_AT_PR_63,
        **LOWERED_AT_PR_68}[which]
