"""The metrics that read device scopes: the wire-format reader of a
trace's event metadata on the v5e fixture, ``device_scope_share`` over it
under a stand-in taxonomy, its refusals, and the metric files
(``pytest benchmarks/tests``; outside tier-1)."""

from __future__ import annotations

import glob
import os
import shutil

import pytest

from manifest_checks import file_holds_entry
from test_benchmark import BENCH, harness

from tracereduce import scopes, xplane

FIXTURE = os.path.join(BENCH, "tracereduce", "fixtures",
                       "matmul_loop.xplane.pb")
FUSION = "%convolution_tanh_fusion"
SCOPE_METRICS = sorted(
    os.path.basename(p)[:-len(".json")]
    for p in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))
    if harness.read_json(p)["reader"]["kind"] == "device_scope_share")


def _fusion(meta):
    (name,) = [n for n in meta if n.startswith(FUSION + " = ")]
    return name, meta[name]


def test_the_wire_format_reader_finds_the_scope_path_of_the_fixture():
    planes = scopes.device_metadata(FIXTURE)
    assert sorted(planes) == [0]
    name, op = _fusion(planes[0])
    assert op == scopes.OpMeta(
        tf_op="jit(f)/my_block/dot_general:",
        hlo_category="convolution fusion", flops=17188257792,
        bytes_accessed=25165824, program_id=13608157897908181457)
    # every operation of the trace is in the metadata by its text
    trace = xplane.load(FIXTURE)
    assert {e.name for e in trace.devices[0].ops} <= set(planes[0])
    assert name in {e.name for e in trace.devices[0].ops}


def test_the_wire_format_reader_agrees_with_the_protobuf_module():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with open(FIXTURE, "rb") as f:
        space.ParseFromString(f.read())
    (plane,) = [p for p in space.planes if p.name == "/device:TPU:0"]
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    want = {}
    for em in plane.event_metadata.values():
        stats = {}
        for s in em.stats:
            which = s.WhichOneof("value")
            value = getattr(s, which)
            stats[names[s.metadata_id]] = (names[value]
                                           if which == "ref_value" else value)
        want[em.name] = scopes.OpMeta(
            tf_op=stats.get("tf_op", ""),
            hlo_category=stats.get("hlo_category", ""),
            flops=stats.get("flops", 0),
            bytes_accessed=stats.get("bytes_accessed", 0),
            program_id=stats.get("program_id", 0))
    assert scopes.device_metadata(FIXTURE)[0] == want


def test_the_programs_of_the_fixture_agree_with_the_protobuf_module():
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with open(FIXTURE, "rb") as f:
        space.ParseFromString(f.read())
    (plane,) = [p for p in space.planes if p.name == scopes.METADATA_PLANE]
    (meta,) = plane.event_metadata.values()
    proto = hlo_pb2.HloProto()
    proto.ParseFromString(meta.stats[0].bytes_value)
    (program,) = scopes.hlo_programs(FIXTURE).values()
    assert f"({next(iter(scopes.hlo_programs(FIXTURE)))})" in meta.name

    def elements(shape):
        if shape.tuple_shapes:
            return sum(map(elements, shape.tuple_shapes))
        out = 1
        for d in shape.dimensions:
            out *= d
        return out

    want = {c.id: [scopes.Instruction(
        name=i.name, opcode=i.opcode, op_name=i.metadata.op_name,
        elements=elements(i.shape), id=i.id, operands=tuple(i.operand_ids),
        called=tuple(i.called_computation_ids)) for i in c.instructions]
        for c in proto.hlo_module.computations}
    assert program == want


def test_an_instruction_without_a_path_takes_its_fusions_or_its_neighbours():
    def I(name, opcode, op_name="", elements=8, id=0, operands=(),
          called=()):
        return scopes.Instruction(name, opcode, op_name, elements, id,
                                  operands, called)

    computations = {
        1: [I("p", "parameter", id=1),
            # the compiler's own fusion: no path; inside, the rotary of
            # attn.proj outweighs a convert of the norm
            I("fusion.9", "fusion", id=2, operands=(1,), called=(2,)),
            I("copy.1", "copy", id=3, operands=(2,)),         # its layout
            I("ffn_fusion", "fusion", "jit(f)/nxd.ffn/add", id=4,
              operands=(3,), called=(3,)),
            I("iota.7", "iota", id=5)],                       # no neighbour
        2: [I("a", "multiply", "jit(f)/nxd.attn/nxd.attn.proj/mul", 64),
            I("b", "convert", "jit(f)/nxd.norm/convert", 8)],
        3: [I("x", "parameter", id=1, elements=4096),
            I("w", "parameter", id=2, elements=1 << 20),
            # a matmul inside counts its operands: the weights it streams
            I("c", "convolution", "jit(f)/nxd.ffn/nxd.ffn.dense/dot", 4096,
              id=3, operands=(1, 2)),
            I("d", "add", "jit(f)/nxd.ffn/add", 8192, id=4)]}
    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    got = scopes.resolved_paths(computations, scope_of)
    assert {k: (scope_of(v[0]), v[1]) for k, v in got.items()
            if k in ("fusion.9", "copy.1", "ffn_fusion", "iota.7", "p")} == {
        "fusion.9": ("attn.proj", "body"),
        "copy.1": ("attn.proj", "neighbour"),
        "ffn_fusion": ("ffn", "own"),
        "iota.7": ("(unscoped)", "none"),
        "p": ("attn.proj", "neighbour")}
    # a fusion with no path of its own reads its heaviest: the matmul
    computations[1][3].op_name = ""
    got = scopes.resolved_paths(computations, scope_of)
    assert (scope_of(got["ffn_fusion"][0]), got["ffn_fusion"][1]) == (
        "ffn.dense", "body")


def test_a_conditional_reads_its_branches_and_they_read_it():
    def I(name, opcode, op_name="", elements=8, id=0, operands=(),
          called=()):
        return scopes.Instruction(name, opcode, op_name, elements, id,
                                  operands, called)

    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    summarise = "jit(f)/nxd.attn/nxd.attn.summarise/"
    computations = {
        1: [I("pred", "parameter", id=1),
            # the compiler rewrote the conditional and left it no path
            I("cond.3", "conditional", id=2, operands=(1,), called=(2, 3)),
            # a loop the program did not mark gives its body nothing
            I("while.1", "while", "jit(f)/while", id=3, called=(4,))],
        2: [I("sum", "fusion", summarise + "reduce_sum", 64, id=1)],
        # the branch not taken: zeros of the compiler's own
        3: [I("zero", "constant", id=1),
            I("broadcast.39.clone", "broadcast", id=2, operands=(1,))],
        4: [I("iota.2", "iota", id=1)]}
    got = scopes.resolved_paths(computations, scope_of)
    assert {k: (scope_of(v[0]), v[1]) for k, v in got.items()} == {
        "pred": ("attn.summarise", "neighbour"),
        "cond.3": ("attn.summarise", "body"),
        "while.1": ("(unscoped)", "none"),
        "sum": ("attn.summarise", "own"),
        "zero": ("attn.summarise", "caller"),
        "broadcast.39.clone": ("attn.summarise", "caller"),
        "iota.2": ("(unscoped)", "none")}


@pytest.mark.parametrize("text,want", [
    ("%cond.3 = (bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[8]{0}) "
     "conditional(%p, %a, %b), branch_computations={%r1, %r2}", True),
    ("%while.7 = (s32[], bf16[4]{0}) while(%tuple.1), body=%b", True),
    ("%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%while.7), "
     "kind=kLoop, calls=%fused", False),
    ("%cond_sum = f32[8]{0:T(256)} custom-call(%x)", False),
    ("while.2", True)])
def test_a_container_is_known_by_its_opcode(text, want):
    assert scopes.is_container(text) is want


def test_a_negative_int64_and_a_reference_are_read():
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(number, payload, wire=scopes.BYTES):
        head = varint(number << 3 | wire)
        return head + (varint(len(payload)) + payload
                       if wire == scopes.BYTES else payload)

    def entry(key, message):
        return field(scopes.MAP_KEY, varint(key), scopes.VARINT) + field(
            scopes.MAP_VALUE, message)

    stat_names = {1: b"tf_op", 2: b"flops", 3: b"jit(f)/nxd.ffn/add:"}
    plane = b"".join(
        field(scopes.PLANE_STAT_METADATA,
              entry(k, field(scopes.META_NAME, v)))
        for k, v in stat_names.items())
    stats = (field(scopes.META_STATS,
                   field(scopes.STAT_ID, varint(1), scopes.VARINT)
                   + field(scopes.STAT_REF, varint(3), scopes.VARINT))
             + field(scopes.META_STATS,
                     field(scopes.STAT_ID, varint(2), scopes.VARINT)
                     + field(scopes.STAT_INT, varint((1 << 64) - 5),
                             scopes.VARINT)))
    plane += field(scopes.PLANE_EVENT_METADATA, entry(
        7, field(scopes.META_NAME, b"%add.1 = f32[8]{0} add(..)") + stats))
    meta = scopes.plane_metadata(memoryview(plane))
    assert meta == {"%add.1 = f32[8]{0} add(..)": scopes.OpMeta(
        tf_op="jit(f)/nxd.ffn/add:", flops=-5)}


@pytest.fixture
def traced(tmp_path):
    """The fixture as a traced run would leave it, and what the runner
    would hand a reader of it."""
    at = tmp_path / "out" / "some-cell" / "trace" / "plugins" / "profile"
    at.mkdir(parents=True)
    shutil.copy(FIXTURE, at / "host.xplane.pb")
    trace = xplane.load(FIXTURE)
    ops = trace.devices[0].ops
    window = (min(e.start for e in ops), max(e.end for e in ops))
    obs = harness.Observations(config={}, peaks=None, chips=1)
    obs.trace, obs.reduction = trace, xplane.reduce(trace, window)
    return obs, str(tmp_path / "out")


def test_the_share_of_a_scope_over_the_fixture(traced, monkeypatch):
    from neuronx_distributed_tpu.obs import device_scopes

    obs, out_dir = traced
    reader = harness.load_plugin("readers", "device_scope_share")
    # the fixture's program marked one block, by another name: a stand-in
    # taxonomy reads it as the feed-forward's dense child
    monkeypatch.setattr(
        device_scopes, "scope_of",
        lambda path: "ffn.dense" if "/my_block/" in path else "(unscoped)")
    share = {s: reader.read({"scopes": [s]}, obs, out_dir)
             for s in ("ffn", "ffn.dense", "attn", "(unscoped)")}
    busy = obs.reduction.busy_by_device[0]
    fusion = sum(e.end - e.start for e in obs.trace.devices[0].ops
                 if e.name.startswith(FUSION + " = "))
    # the fusion by its tf_op; the weights' prefetch (copy-start and
    # copy-done, the compiler's own: no tf_op) by the matmul that reads it
    assert 50.0 < 100.0 * fusion / busy < share["ffn"]
    assert share["ffn"] == share["ffn.dense"] == pytest.approx(100.0,
                                                               abs=0.5)
    assert share["attn"] == 0.0 and share["(unscoped)"] == 0.0
    by_scope, _, by_route = scopes.seconds_by_scope(
        obs.trace, obs.reduction.window,
        scopes.event_scopes(FIXTURE, 0, device_scopes.scope_of))
    assert set(by_scope) == {"ffn.dense"}
    assert set(by_route) == {"tf_op", "neighbour"}
    assert by_route["tf_op"] == pytest.approx(fusion)


def test_the_reader_refuses_what_it_cannot_read_and_says_why(
        traced, monkeypatch, capsys):
    from neuronx_distributed_tpu.obs import device_scopes

    obs, out_dir = traced
    reader = harness.load_plugin("readers", "device_scope_share")
    # no marker of the program's anywhere in the trace
    assert reader.read({"scopes": ["ffn"]}, obs, out_dir) is None
    assert "carries a device scope" in capsys.readouterr().out
    monkeypatch.setattr(device_scopes, "scope_of",
                        lambda path: "ffn" if "my_block" in path else "x")
    # an operation of the trace that the file's metadata does not hold
    extra = xplane.Event("%fusion.9 = f32[8]{0} fusion(..)", 0.0, 1e-6)
    obs.trace.devices[0].ops.append(extra)
    assert reader.read({"scopes": ["ffn"]}, obs, out_dir) is None
    assert "not in the metadata" in capsys.readouterr().out
    obs.trace.devices[0].ops.remove(extra)
    assert reader.read({"scopes": ["ffn"]}, obs, out_dir) > 0.0
    # no trace file, no device plane, no trace
    assert reader.read({"scopes": ["ffn"]}, obs,
                       os.path.join(out_dir, "nowhere")) is None
    obs.trace = None
    assert reader.read({"scopes": ["ffn"]}, obs, out_dir) is None


def test_a_program_without_the_scopes_gives_nothing(traced, monkeypatch):
    import sys

    obs, out_dir = traced
    reader = harness.load_plugin("readers", "device_scope_share")
    monkeypatch.setitem(
        sys.modules, "neuronx_distributed_tpu.obs.device_scopes", None)
    assert reader.read({"scopes": ["ffn"]}, obs, out_dir) is None


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_a_scope_metric_names_scopes_of_the_tuple(name):
    from neuronx_distributed_tpu.obs.device_scopes import SCOPES, UNSCOPED

    spec = harness.read_json(harness.data_file("layer_metrics", name))
    entry = harness.by_name(harness.load_manifest()["per_layer"], name,
                            "metric")
    assert file_holds_entry(spec, entry)
    assert spec["source"] == "device_trace" and spec["unit"] == "%"
    assert spec["reader"]["scopes"]
    assert set(spec["reader"]["scopes"]) <= set(SCOPES) | {UNSCOPED}


def test_every_scope_metric_file_is_a_metric_of_the_manifest():
    """Ten when the scopes came (PR 36); every family since brought its
    own. Whatever their number, each file is a metric of the manifest."""
    listed = {x["name"] for x in harness.load_manifest()["per_layer"]}
    assert SCOPE_METRICS and set(SCOPE_METRICS) <= listed
