"""Tier-4 mesh-protocol verifier tests: each bad fixture entry flags
exactly its own rule, the clean counterparts verify silent, every
registered package entry point passes the verifier on the 8-device
virtual mesh (the self-gate), the extracted schedule is stable across
runs and round-trips through JSON, and the CLI exposes it all via
``--mesh-protocol`` / ``--emit-schedule``."""

import json
import os
import runpy
import subprocess
import sys

import pytest

from neuronx_distributed_tpu.analysis import mesh_protocol
from neuronx_distributed_tpu.analysis.audit_registry import (
    BuiltEntry, get_entry_point, load_default_entry_points,
    register_entry_point)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "analysis_fixtures")
BAD = os.path.join(FIXTURES, "bad_mesh_protocol.py")
GOOD = os.path.join(FIXTURES, "good_mesh_protocol.py")

MESH_RULES = {"jaxpr-collective-divergence", "jaxpr-ring-malformed",
              "jaxpr-silent-replication", "jaxpr-implicit-gather"}

PACKAGE_ENTRIES = {"train-step", "engine-step", "ep-dispatch-ring",
                   "ring-attention", "ring-attention-int8",
                   "flash-decoding", "ulysses-attention"}


# ---------------------------------------------------------------------------
# exact corpus: one bad + one good fixture entry per rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,rule", [
    ("fixture-divergent-cond", "jaxpr-collective-divergence"),
    ("fixture-bad-ring", "jaxpr-ring-malformed"),
    ("fixture-silent-replication", "jaxpr-silent-replication"),
    ("fixture-implicit-gather", "jaxpr-implicit-gather"),
])
def test_bad_fixture_flags_exactly_its_rule(name, rule):
    runpy.run_path(BAD)
    fs, schedule = mesh_protocol.audit_entry_point(get_entry_point(name))
    assert {f.rule for f in fs} == {rule}, \
        "\n".join(f.format() for f in fs)
    assert schedule is not None  # the trace itself succeeded
    # findings anchor at the fixture's registration site
    assert all(f.path.endswith("bad_mesh_protocol.py") for f in fs)
    assert all(f.line > 1 for f in fs)


@pytest.mark.parametrize("name", [
    "fixture-symmetric-cond", "fixture-good-ring",
    "fixture-no-replication", "fixture-contract-ok",
])
def test_good_fixture_verifies_clean(name):
    runpy.run_path(GOOD)
    fs, schedule = mesh_protocol.audit_entry_point(get_entry_point(name))
    assert fs == [], "\n".join(f.format() for f in fs)
    assert schedule is not None


def test_benign_cond_with_pbroadcast_bookkeeping_not_divergent():
    """shard_map's replication checker inserts pbroadcast into cond
    branches; it moves zero wire bytes and must not count as schedule
    divergence (or every benign cond would flag)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec

    @register_entry_point("fixture-benign-cond")
    def _build():
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("ep",))

        def body(x, flag):
            return jax.lax.cond(flag > 0, lambda b: b + 1.0,
                                lambda b: b * 2.0, x)

        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(PartitionSpec("ep", None), PartitionSpec()),
            out_specs=PartitionSpec("ep", None)))
        return BuiltEntry(fn=fn, args=(jnp.zeros((8, 64), jnp.float32),
                                       jnp.zeros((), jnp.int32)))

    fs, schedule = mesh_protocol.audit_entry_point(
        get_entry_point("fixture-benign-cond"))
    assert fs == [], "\n".join(f.format() for f in fs)
    assert schedule == []  # pbroadcast is bookkeeping, not wire traffic


def test_build_failure_becomes_audit_error_finding():
    @register_entry_point("fixture-mp-broken")
    def _build():
        raise RuntimeError("no mesh today")

    fs, schedule = mesh_protocol.audit_entry_point(
        get_entry_point("fixture-mp-broken"))
    assert [f.rule for f in fs] == ["jaxpr-audit-error"]
    assert "no mesh today" in fs[0].message
    assert schedule is None


# ---------------------------------------------------------------------------
# self-gate: the package's own entry points obey the protocol
# ---------------------------------------------------------------------------

def test_all_package_entry_points_verify_clean():
    eps = load_default_entry_points()
    assert PACKAGE_ENTRIES <= set(eps)
    fs, schedules = mesh_protocol.audit_entry_points(
        names=sorted(PACKAGE_ENTRIES))
    assert fs == [], "\n".join(f.format() for f in fs)
    assert set(schedules) == PACKAGE_ENTRIES


def test_ring_attention_schedule_shape():
    _, schedules = mesh_protocol.audit_entry_points(
        names=["ring-attention"])
    ops = schedules["ring-attention"]
    # the k and v hops of the rotating scan, cp-1 trips each
    assert [op.prim for op in ops] == ["ppermute", "ppermute"]
    assert all(op.axes == ("cp",) for op in ops)
    assert all(op.trips == 3 for op in ops)
    assert all(op.scope == "shard_map/scan" for op in ops)
    assert all(op.payload_bytes > 0 for op in ops)


# ---------------------------------------------------------------------------
# schedule artifact: JSON round-trip + determinism
# ---------------------------------------------------------------------------

def test_schedule_json_round_trips_and_is_stable():
    names = ["ring-attention", "flash-decoding"]
    _, s1 = mesh_protocol.audit_entry_points(names=names)
    _, s2 = mesh_protocol.audit_entry_points(names=names)
    j1 = mesh_protocol.schedules_to_json(s1)
    j2 = mesh_protocol.schedules_to_json(s2)
    assert j1 == j2  # two runs, byte-identical artifact
    doc = json.loads(j1)
    assert doc["version"] == 1
    assert set(doc["entries"]) == set(names)
    for ops in doc["entries"].values():
        assert [o["seq"] for o in ops] == list(range(len(ops)))
        for o in ops:
            assert set(o) == {"seq", "prim", "axes", "shape", "dtype",
                              "payload_bytes", "trips", "scope"}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

CLI = [sys.executable, "-m", "neuronx_distributed_tpu.analysis"]


def _cli(*args):
    return subprocess.run([*CLI, *args], cwd=REPO, capture_output=True,
                          text=True)


def test_cli_mesh_protocol_register_fixture_fails():
    r = _cli("--mesh-protocol", "--register", BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    for rid in MESH_RULES:
        assert rid in r.stdout, rid
    # --register replaces the default registry: only the fixture ran
    assert "train-step" not in r.stdout


def test_cli_emit_schedule_writes_stable_json(tmp_path):
    out1, out2 = str(tmp_path / "s1.json"), str(tmp_path / "s2.json")
    # two interpreters of their own, side by side: what is held is that
    # two runs write the same bytes, not that one waits for the other
    runs = [subprocess.Popen(
        [*CLI, "--mesh-protocol", "--register", GOOD, "--emit-schedule", out],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for out in (out1, out2)]
    for run in runs:
        stdout, stderr = run.communicate()
        assert run.returncode == 0, stdout + stderr
    with open(out1) as f1, open(out2) as f2:
        b1, b2 = f1.read(), f2.read()
    assert b1 == b2
    doc = json.loads(b1)
    # the fixture entries are present (package modules imported by the
    # fixture's own import chain may register more)
    assert {"fixture-symmetric-cond", "fixture-good-ring",
            "fixture-no-replication",
            "fixture-contract-ok"} <= set(doc["entries"])


def test_cli_list_rules_includes_mesh_protocol_tier():
    r = _cli("--list-rules")
    assert r.returncode == 0
    for rid in MESH_RULES:
        assert f"{rid}:" in r.stdout
        assert "[--mesh-protocol]" in r.stdout


def test_cli_explain_mesh_protocol_rule():
    r = _cli("--explain", "jaxpr-collective-divergence")
    assert r.returncode == 0
    assert "deadlock" in r.stdout
