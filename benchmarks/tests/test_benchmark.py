"""The benchmark's own tests, on the CPU: ``pytest benchmarks/tests``.

Not part of the repository's tier-1 suite (which collects ``tests/``).
The runners are rehearsed at toy sizes through
``benchmarks/tests/manifest.json``; nothing here is a measurement.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness  # noqa: E402
from generators import requests as gen_requests  # noqa: E402
from generators import token_batches  # noqa: E402
from runners import models  # noqa: E402
from tracereduce import xplane  # noqa: E402

MANIFESTS = [harness.MANIFEST, os.path.join(HERE, "manifest.json")]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _run(args, env=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=timeout)


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


# -- the manifest -------------------------------------------------------------

@pytest.mark.parametrize("path", MANIFESTS)
def test_manifest_keeps_the_contract(path):
    m = harness.load_manifest(path)
    assert set(m) == TOP_KEYS
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert all(not w.startswith("/") and ".." not in w for w in m["command"])
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    for n in names:
        assert harness.NAME.match(n), n
    for g in ("configs", "workloads"):
        assert len({x["name"] for x in m[g]}) == len(m[g])
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert 1 <= len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in m["workloads"])
    cells = {w["name"] for w in m["workloads"]}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert harness.NAME.match(w["traffic"])
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert harness.UNIT.match(x["unit"]) and 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
        assert x["better"] in ("lower", "higher")
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert harness.UNIT.match(x["unit"]) and x["source"] in SOURCES
        assert x["moves"] in e2e and x["better"] in ("lower", "higher")
        moved = e2e[x["moves"]]
        for cell in x.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (x["name"], cell)
    for cell in cells:
        assert len(harness.metrics_of(m, "end_to_end", cell)) >= 2
        assert len(harness.metrics_of(m, "per_layer", cell)) >= 1


@pytest.mark.parametrize("path", MANIFESTS)
def test_every_name_resolves_to_its_files(path):
    sys.path.insert(0, BENCH)
    import run as bench_run

    m = harness.load_manifest(path)
    for w in m["workloads"]:
        cell = bench_run.load_cell(path, w["name"])
        assert cell.config["runner"] in ("serve", "train")
        assert cell.config["chips"] == w["chips"]
        harness.load_plugin("runners", cell.config["runner"]).run
        gen = harness.load_plugin("generators", cell.traffic["kind"])
        assert callable(gen.generate)
        family = harness.load_plugin("families", cell.config["family"])
        assert callable(family.build) and callable(family.published)
        reference = models.reference(cell.config)
        assert callable(reference.forward)
        assert callable(reference.cross_entropy)
    for x in m["per_layer"]:
        spec = harness.read_json(harness.data_file("layer_metrics", x["name"]))
        for k in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[k] == x[k], (x["name"], k)
        assert callable(harness.load_plugin(
            "readers", spec["reader"]["kind"]).read)


# the published (hidden, intermediate, heads, kv heads) of the configurations
# this file knows; one it does not know is held to the general rules alone
PUBLISHED_WIDTHS = {"mixtral-8x7b": (4096, 14336, 32, 8),
                    "mistral-7b": (4096, 14336, 32, 8),
                    "mistral-7b-serve": (4096, 14336, 32, 8)}
WIDTH_KEY = re.compile(r"hidden_size|intermediate|latent|state|proj|head_|"
                       r"_dim$|_rank$|expan|per_tok")


def _check_configurations(manifest: dict):
    """Every configuration states its source, what it stands for and what
    it assumed, cuts no width, and runs at the size its ``reduced`` says."""
    for c in manifest["configs"]:
        cfg = harness.read_json(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == list(cfg["reduced"]), c["name"]
        assert cfg["source"] == c["source"] and "stands_for" in cfg
        assert "assumed" in cfg and not cfg.get("rehearsal")
        for key, cut in cfg["reduced"].items():
            assert not WIDTH_KEY.search(key), (c["name"], key)
            assert cut["to"] == cfg[key] != cut["from"], (c["name"], key)
        if c["name"] in PUBLISHED_WIDTHS:
            assert (cfg["hidden_size"], cfg["intermediate_size"],
                    cfg["num_attention_heads"], cfg["num_key_value_heads"]
                    ) == PUBLISHED_WIDTHS[c["name"]]
            assert c["reduced"] == ["num_hidden_layers"]


def test_configurations_cut_depth_only(tmp_path):
    m = harness.load_manifest()
    assert set(PUBLISHED_WIDTHS) <= {c["name"] for c in m["configs"]}
    _check_configurations(m)
    # a later PR's configuration of other widths, uncut, passes as it comes
    added = dict(harness.read_json(os.path.join(
        ROOT, "benchmarks", "configs", "mistral-7b-serve.json")),
        source="https://example.org/added", hidden_size=2048,
        intermediate_size=1024, num_attention_heads=16,
        num_key_value_heads=16, num_hidden_layers=16, reduced={})
    (tmp_path / "added.json").write_text(json.dumps(added))
    m["configs"].append({"name": "added", "source": added["source"],
                         "file": str(tmp_path / "added.json"), "reduced": [],
                         "why": "added by the test"})
    _check_configurations(m)
    # and one that cuts a width does not
    added["reduced"] = {"hidden_size": {"from": 4096, "to": 2048}}
    (tmp_path / "added.json").write_text(json.dumps(added))
    m["configs"][-1]["reduced"] = ["hidden_size"]
    with pytest.raises(AssertionError):
        _check_configurations(m)
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v4")


# -- generators -----------------------------------------------------------------

def test_request_lengths_match_the_stated_distribution():
    chat = harness.read_json(harness.data_file("traffic", "chat"))
    offline = harness.read_json(harness.data_file("traffic", "offline-chat"))
    assert chat["prompt_tokens"] == offline["prompt_tokens"]
    assert chat["answer_tokens"] == offline["answer_tokens"]
    p = gen_requests.lengths(chat["prompt_tokens"], 1000)
    a = gen_requests.lengths(chat["answer_tokens"], 1000)
    assert abs(np.median(p) - 384) <= 2 and abs(np.median(a) - 96) <= 1
    assert p.min() == 32 and p.max() == 1920
    assert a.min() >= 8 and a.max() == 384
    assert p.max() + a.max() <= 20 * 128          # max_blocks_per_seq
    sigma = np.std(np.log(p[(p > 32) & (p < 1920)]))
    assert 0.6 < sigma < 0.8                      # 0.8, less the clipped tails


def test_requests_are_a_fixed_set_in_a_seeded_order():
    mix = harness.read_json(harness.data_file("traffic", "chat"))
    a = gen_requests.generate(mix, 7, 32000, 30)
    b = gen_requests.generate(mix, 7, 32000, 30)
    c = gen_requests.generate(mix, 2 ** 31 + 11, 32000, 30)
    key = lambda g: [(r.arrival_s, r.prompt, r.max_new_tokens)
                     for r in g["requests"]]
    assert key(a) == key(b) and key(a) != key(c)
    lens = lambda g: sorted(len(r.prompt) for r in g["requests"])
    outs = lambda g: sorted(r.max_new_tokens for r in g["requests"])
    gaps = lambda g: np.sort(np.diff([r.arrival_s for r in g["requests"]]))
    assert lens(a) == lens(c) and outs(a) == outs(c)
    np.testing.assert_allclose(gaps(a), gaps(c), rtol=0, atol=1e-9)
    n = len(a["requests"])
    rate = mix["arrivals"]["rate_per_s"]
    assert n == round(rate * (mix["lead_in_s"] + 30))
    assert a["requests"][-1].arrival_s < mix["lead_in_s"] + 30
    assert a["open_loop"] and all(
        0 <= t < 32000 for r in a["requests"][:5] for t in r.prompt)
    off = gen_requests.generate(
        harness.read_json(harness.data_file("traffic", "offline-chat")),
        3, 32000, 30)
    assert not off["open_loop"]
    assert all(r.arrival_s == 0.0 for r in off["requests"])


def test_token_batches_are_seeded_and_shifted():
    mix = harness.read_json(harness.data_file("traffic", "packed-4k"))
    assert mix["batch"] * mix["seq_len"] == 8192
    it = token_batches.generate(mix, 5, 32768, 10)
    b0, b1 = next(it), next(it)
    again = token_batches.batch(mix, 5, 32768, 1)
    assert b0["input_ids"].shape == (2, 4096)
    assert b0["input_ids"].dtype == np.int32
    np.testing.assert_array_equal(b0["input_ids"][:, 1:], b0["labels"][:, :-1])
    np.testing.assert_array_equal(b1["input_ids"], again["input_ids"])
    assert not np.array_equal(b0["input_ids"], b1["input_ids"])
    assert b0["input_ids"].max() < 32768


# -- trace reduction ----------------------------------------------------------------

FIXTURE = os.path.join(BENCH, "tracereduce", "fixtures",
                       "matmul_loop.xplane.pb")


def test_tracereduce_on_the_recorded_fixture():
    """Six runs of one 2048^3 bf16 matmul on a TPU v5e, 2 ms of host sleep
    after each (recorded by PR 23's probe)."""
    trace = xplane.load(FIXTURE)
    assert sorted(trace.devices) == [0]
    tl = trace.devices[0]
    assert len(tl.modules) == 6 and len(tl.ops) == 18
    assert trace.offset_s == pytest.approx(1.304e-3, abs=2e-6)
    steps = [e for e in trace.annotations if e.name == "bench/step"]
    gaps = [e for e in trace.annotations if e.name == "bench/host_gap"]
    assert len(steps) == 6 and len(gaps) == 6
    window = (steps[0].start, gaps[-1].end)
    red = xplane.reduce(trace, window, gap_layer=("bench/",))
    assert red.window_s == pytest.approx(19.82e-3, rel=1e-3)
    assert red.busy_s == pytest.approx(6 * 91.58e-6, rel=2e-3)
    assert red.op_seconds["convolution_tanh_fusion bf16[2048,2048]"] == pytest.approx(
        6 * 91.566e-6, rel=1e-3)
    idle = dict(red.idle_gaps)
    assert sum(idle.values()) + red.busy_s == pytest.approx(red.window_s)
    assert idle["bench/host_gap"] == pytest.approx(14.47e-3, rel=1e-2)
    assert idle["bench/host_gap"] > idle["bench/step"] > 0
    hit = xplane.matching(trace, "convolution", window)
    assert hit["count"] == 6
    assert hit["total"] == hit["exposed"] == pytest.approx(
        6 * 91.566e-6, rel=1e-3)
    # every run starts on the device after the host began its step
    for m, s in zip(tl.modules, steps):
        assert s.start < m.start < s.end


def test_interval_arithmetic():
    assert xplane.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.subtract([(0, 10), (20, 30)],
                           [(1, 2), (5, 6), (9, 21), (25, 26)]) == [
        (0, 1), (2, 5), (6, 9), (21, 25), (26, 30)]
    assert xplane.total(xplane.clip([(0, 5), (7, 9)], 4, 8)) == 2
    text = "%fusion.12 = bf16[8,4]{1,0:T(8,128)} fusion(bf16[8]{0} %p)"
    assert xplane.stable_name(text) == "fusion bf16[8,4]"
    assert xplane.op_kind("%while.2 = (s32[]{:T(128)}, bf16[1,8]) while(") \
        == "while"


def test_span_self_time_excludes_children():
    ev = [{"name": "a", "ts": 0.0, "dur": 100.0},
          {"name": "b", "ts": 10.0, "dur": 30.0},
          {"name": "c", "ts": 15.0, "dur": 10.0},
          {"name": "b", "ts": 50.0, "dur": 20.0}]
    got = harness.span_self_times(ev)
    assert got == pytest.approx({"a": 50e-6, "b": 40e-6, "c": 10e-6})


# -- readers and the yardstick's arithmetic -------------------------------------------

def _obs(**kw):
    cfg = harness.read_json(os.path.join(BENCH, "configs",
                                         "mixtral-8x7b.json"))
    return harness.Observations(config=cfg,
                                peaks=harness.peaks_for("TPU v5 lite"),
                                chips=1, **kw)


def test_work_functions():
    from readers import work

    # one slot decoding at 255 resident tokens, one new slot with a
    # 128-row chunk, over two steps
    obs = _obs(series={"traced_slot_lengths": [
        np.array([254, 0]), np.array([255, 128]), np.array([256, 256])]})
    flops, nbytes = work.paged_attention(obs)
    per_pos = 4 * 32 * 128
    want_attended = (256 + (128 * 128 - 128 * 127 / 2)
                     + 257 + (128 * 256 - 128 * 127 / 2))
    assert flops == pytest.approx(per_pos * want_attended * 4)
    assert nbytes == pytest.approx((256 + 128 + 257 + 256) * 8 * 128 * 4 * 4)
    tr = harness.Observations(
        config=harness.read_json(os.path.join(BENCH, "configs",
                                              "mistral-7b.json")),
        peaks=None, chips=4,
        scalars={"matmul_params": 2.5e9, "seq_len": 4096.0})
    assert work.decoder_train(tr) == pytest.approx(
        6 * 2.5e9 + 6 * 11 * 4096 * 4096)


def test_readers_return_none_when_there_is_nothing_to_read():
    obs = _obs()
    for m in harness.load_manifest()["per_layer"]:
        spec = harness.read_json(harness.data_file("layer_metrics", m["name"]))
        reader = harness.load_plugin("readers", spec["reader"]["kind"])
        assert reader.read(spec["reader"], obs) is None, m["name"]


def test_probe_schedule_feeds_every_position_once():
    from runners.serve import probe_schedule

    steps = probe_schedule(256, 8, 128)
    seen = [rp for rows in steps for rp in rows]
    assert sorted(seen) == [(s, p) for s in (0, 1) for p in range(264)]
    assert all(len(rows) <= 128 for rows in steps)
    # a prefill chunk beside a decode row, and decode rows among pads
    assert any(len(rows) == 128 and rows[0] == (0, 256) for rows in steps)
    assert steps[-1] == [(0, 263), (1, 263)] or len(steps[-1]) <= 2


# -- the reference against the package's models ---------------------------------------

TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 128}
TINY_MOE = dict(TINY, num_local_experts=8, num_experts_per_tok=2)


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_reference_agrees_with_the_package(family):
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32)
    if family == "llama":
        config = dict(TINY, family="llama")
    else:
        # 8 experts at a capacity that drops nothing: the published block
        config = dict(TINY_MOE, family="mixtral")
        kw["capacity_factor"] = 4.0
    cfg, model, _ = models.build(config, **kw)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 48))
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))
    params = harness.make_weights(shapes, 2 ** 31 + 5, 0.05)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(ids))
    got = got[0] if isinstance(got, tuple) else got
    reference = models.reference(config)
    assert reference.__name__ == "reference.decoder_f32"
    want, margins = reference.forward(models.published(params, config), ids,
                                      config)
    assert (margins is None) == (family == "llama")
    # float32 on both sides: only the order of sums differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5 * float(np.std(want)) * 50)
    labels = np.roll(ids, -1, axis=1)
    loss = float(reference.cross_entropy(want, labels))
    assert abs(loss - np.log(cfg.vocab_size)) < 0.5
    ps.destroy_model_parallel()


def _tree(config, form, rng):
    """The package's tree for ``config`` from seeded published tensors,
    with gate and up stored in ``form``."""
    L, H, I = (config["num_hidden_layers"], config["hidden_size"],
               config["intermediate_size"])
    E = config.get("num_local_experts")
    D = H // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * D
    w = lambda *shape: rng.normal(0, 0.05, shape).astype(np.float32)
    lead = (L, E) if E else (L,)
    gate, up, down = w(*lead, H, I), w(*lead, H, I), w(*lead, I, H)
    fused, two = ("gate_up", ("gate", "up")) if E else (
        "gate_up_kernel", ("gate_kernel", "up_kernel"))
    mlp = {"H2I": {fused: np.stack([gate, up], -2)},
           "2HI": {fused: np.stack([gate, up], -3)},
           "H2I_flat": {fused: np.concatenate([gate, up], -1)},
           "two_leaves": {two[0]: gate, two[1]: up}}[form]
    if E:
        block = {"moe": {"router": {"kernel": w(L, H, E)},
                         "experts": dict(mlp, down=down)}}
    else:
        block = {"mlp": dict(mlp, down={"kernel": down})}
    layer = dict(block,
                 attn={"qkv": {"q_kernel": w(L, H, H), "k_kernel": w(L, H, kv),
                               "v_kernel": w(L, H, kv)},
                       "o_proj": {"kernel": w(L, H, H)}},
                 input_norm={"scale": 1 + w(L, H)},
                 post_norm={"scale": 1 + w(L, H)})
    V = config["vocab_size"]
    return {"params": {"lm_head": {"kernel": w(H, V)},
                       "model": {"embed": {"embedding": w(V, H)},
                                 "norm": {"scale": 1 + w(H)},
                                 "layers": {"layer": layer}}}}


FORMS = ["H2I", "2HI", "H2I_flat", "two_leaves"]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_reference_reads_gate_and_up_in_any_stored_form(family, form):
    """``[H,2,I]`` (what the package stores today), ``[2,H,I]``, ``[H,2I]``
    and two leaves, dense and expert: the same published tensors, so the
    reference's logits agree."""
    config = dict(TINY if family == "llama" else TINY_MOE, family=family)
    ids = np.random.default_rng(3).integers(0, config["vocab_size"], (2, 24))
    reference = models.reference(config)
    logits = {}
    for f in dict.fromkeys(["H2I", form]):
        tree = _tree(config, f, np.random.default_rng(11))
        logits[f], _ = reference.forward(models.published(tree, config), ids,
                                         config)
    assert float(np.std(logits["H2I"])) > 0.01
    np.testing.assert_allclose(np.asarray(logits[form]),
                               np.asarray(logits["H2I"]), rtol=0, atol=1e-6)
    from families import llama

    with pytest.raises(ValueError):
        llama.gate_or_up({"gate_up": np.zeros((2, 3, 8, 16))}, 0, 0, 8, 8)
    with pytest.raises(ValueError):
        llama.gate_or_up({"down": np.zeros((2, 8, 16))}, 0, 1, 8, 16)


# -- the command -----------------------------------------------------------------------

def test_no_tpu_is_a_nonzero_exit_and_no_result():
    for cell in ("mixtral-8x7b.serve-batch", "mistral-7b.train-tp4",
                 "mistral-7b.serve-batch"):
        p = _run(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
        assert p.returncode != 0
        assert "needs a TPU" in p.stderr
        assert not any(line.startswith("{") for line in p.stdout.splitlines())
    p = _run(["--workload", "no-such-cell"])
    assert p.returncode != 0 and "no workload named" in p.stderr


def test_rehearsal_configuration_cannot_be_a_real_cell(tmp_path):
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    p = _run(["--workload", "tiny-mixtral.serve-batch", "--seconds", "1"])
    assert p.returncode != 0          # not in BENCHMARK.json
    assert all(c["file"].startswith("benchmarks/tests/configs/")
               for c in m["configs"])


@pytest.mark.parametrize("cell,trace,env", [
    ("tiny-mixtral.serve-batch", "0", {}),
    ("tiny-mixtral.serve-chat", "1", {}),
    ("tiny-mistral.serve-batch", "0", {}),
    ("tiny-mistral.serve-batch", "1", {}),
    ("tiny-mistral.train-tp4", "0",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}),
    ("tiny-mistral.train-tp4", "1",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}),
])
def test_rehearsal_runs_end_to_end(cell, trace, env):
    manifest = os.path.join(HERE, "manifest.json")
    p = _run(["--manifest", manifest, "--workload", cell, "--seed",
              str(2 ** 31 + 7), "--seconds", "1.5", "--trace", trace], env)
    assert p.returncode == 0, p.stderr[-2000:]
    line = _last_json(p.stdout)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    # each number compared beside its limit: the result's last key, and
    # the last lines of standard error
    assert line["compared"] and all(
        number <= limit for number, limit in line["compared"].values())
    said = p.stderr.strip().splitlines()[-len(line["compared"]):]
    assert said == [f"compared {name} {number} limit {limit}"
                    for name, (number, limit) in line["compared"].items()]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # never under a device metric's name
    assert line["metrics"] and all(k.startswith("rehearsal.")
                                   for k in line["metrics"])
    m = harness.load_manifest(manifest)
    group = "per_layer" if trace == "1" else "end_to_end"
    allowed = {"rehearsal." + x["name"]
               for x in harness.metrics_of(m, group, cell)}
    assert set(line["metrics"]) <= allowed
    if trace == "0":
        assert set(line["metrics"]) == allowed
        assert "compiled_in_window=0" in p.stdout


def test_a_cell_a_mix_and_a_metric_are_added_as_files_only(tmp_path):
    """A new traffic mix, a new per-layer metric over an existing reader
    and a new cell: two data files and three manifest entries; no file
    that was there is edited."""
    added = {
        os.path.join(HERE, "traffic", "added-mix.json"): {
            "kind": "requests",
            "prompt_tokens": {"dist": "fixed", "value": 20},
            "answer_tokens": {"dist": "fixed", "value": 4},
            "arrivals": {"kind": "all_at_zero", "count": 3000},
            "lead_in_s": 0.2},
        os.path.join(HERE, "layer_metrics", "step_p90_ms.added.json"): {
            "name": "step_p90_ms.added", "layer": "model step", "unit": "ms",
            "better": "lower", "source": "program_span",
            "moves": "serve_tok_s",
            "workloads": ["tiny-mixtral.serve-added"],
            "reader": {"kind": "series_stat", "series": "step_latency_s",
                       "stat": "p90", "scale": 1000}}}
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    m["workloads"].append({"name": "tiny-mixtral.serve-added",
                           "config": "tiny-mixtral", "traffic": "added-mix",
                           "chips": 1, "why": "added by the test"})
    for x in m["end_to_end"]:
        if x["name"] == "serve_tok_s":
            x["workloads"].append("tiny-mixtral.serve-added")
    m["per_layer"].append({k: v for k, v in added[
        os.path.join(HERE, "layer_metrics", "step_p90_ms.added.json")].items()
        if k != "reader"})
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(m))
    try:
        for path, body in added.items():
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump(body, f)
        for trace in ("0", "1"):
            p = _run(["--manifest", str(manifest), "--workload",
                      "tiny-mixtral.serve-added", "--seconds", "1",
                      "--trace", trace])
            assert p.returncode == 0, p.stderr[-2000:]
            line = _last_json(p.stdout)
            want = ("rehearsal.step_p90_ms.added" if trace == "1"
                    else "rehearsal.serve_tok_s")
            assert want in line["metrics"] and line["correct"]
    finally:
        for path in added:
            if os.path.exists(path):
                os.remove(path)
        os.rmdir(os.path.join(HERE, "layer_metrics"))


def test_a_family_and_its_reference_are_added_as_files_only(tmp_path):
    """A new architecture: a family file, a reference file, a configuration
    that names both (with a key of its own that only its reference reads)
    and two manifest entries; no file that was there is edited."""
    config = harness.read_json(os.path.join(HERE, "configs",
                                            "tiny-mistral-serve.json"))
    config.update(family="added_family", reference="added_reference",
                  added_logit_scale=1.0)
    added = {
        os.path.join(BENCH, "families", "added_family.py"): (
            "from families import llama\n\n"
            "build = llama.build\n\n\n"
            "def published(params, config):\n"
            "    view = llama.published(params, config)\n"
            "    return lambda name, layer=None, expert=None: view(\n"
            "        name, layer, expert)\n"),
        os.path.join(BENCH, "reference", "added_reference.py"): (
            "from reference import decoder_f32\n\n"
            "cross_entropy = decoder_f32.cross_entropy\n\n\n"
            "def forward(weights, tokens, config):\n"
            "    print('[added_reference] scale',\n"
            "          config['added_logit_scale'], flush=True)\n"
            "    logits, extra = decoder_f32.forward(weights, tokens, config)\n"
            "    return logits * config['added_logit_scale'], extra\n"),
        os.path.join(HERE, "configs", "added-arch.json"): json.dumps(config)}
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    m["configs"].append({"name": "added-arch", "source": "none (rehearsal)",
                         "file": "benchmarks/tests/configs/added-arch.json",
                         "reduced": [], "why": "added by the test"})
    m["workloads"].append({"name": "added-arch.serve-batch",
                           "config": "added-arch", "traffic": "tiny-offline",
                           "chips": 1, "why": "added by the test"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "tiny-mistral.serve-batch" in x.get("workloads", ()):
            x["workloads"].append("added-arch.serve-batch")
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(m))
    try:
        for path, body in added.items():
            assert not os.path.exists(path)
            with open(path, "w") as f:
                f.write(body)
        for trace in ("0", "1"):
            p = _run(["--manifest", str(manifest), "--workload",
                      "added-arch.serve-batch", "--seconds", "1",
                      "--trace", trace])
            assert p.returncode == 0, p.stderr[-2000:]
            line = _last_json(p.stdout)
            want = ("rehearsal.step_ms.batch" if trace == "1"
                    else "rehearsal.serve_tok_s")
            assert want in line["metrics"] and line["correct"]
            assert "[added_reference] scale 1.0" in p.stdout
    finally:
        for path in added:
            if os.path.exists(path):
                os.remove(path)
    # a family or a reference that is not there is the harness's own error
    for key in ("family", "reference"):
        with pytest.raises(harness.BenchError, match="no-such"):
            harness.load_plugin({"family": "families"}.get(key, key),
                                "no-such")


def test_the_control_fails_the_logit_check():
    """``control.py`` at the rehearsal's size: the reference computed with
    fp8 weights, put in the program's place, is not correct; the program
    is (the chip readings at the cells' own sizes are in PERF.md)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--manifest",
         os.path.join(HERE, "manifest.json"), "--workload",
         "tiny-mistral.serve-batch", "--seeds", f"5,{2 ** 31 + 9},77",
         "--kinds", "fp8,tier-int8"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = _last_json(p.stdout)
    assert len(out) == 3
    for seed, r in out.items():
        assert r["program"]["correct"] is True, seed
        assert r["fp8"]["correct"] is False, seed
        # the package's int8 tier runs and reads farther off than bf16
        assert (r["tier-int8"]["prefill"]["median"]
                > r["program"]["prefill"]["median"]), seed
    sound = max(r["program"][part]["median"] for r in out.values()
                for part in ("prefill", "decode"))
    control = min(r["fp8"][part]["median"] for r in out.values()
                  for part in ("prefill", "decode"))
    assert control > 3 * sound
