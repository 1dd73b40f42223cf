"""mxtpu-lint --graph: compiled-artifact contract checking.

Unit leg: every graph rule fires on a hand-built stub record and stays
quiet on its clean twin — jaxprs are duck-typed, so nothing here needs
jax. Integration leg: ONE subprocess ``--graph --json`` run asserts the
trace harness registers the full canonical site set and the shipped
tree is clean against the checked-in contracts (the tier-1 gate: a
reordered collective in overlap.py or a dead donation turns this red).
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tools.mxtpu_lint import apply_baseline, write_baseline  # noqa: E402
from tools.mxtpu_lint.__main__ import main as lint_main  # noqa: E402
from tools.mxtpu_lint.graphcheck import (  # noqa: E402
    CONTRACTS_RELPATH, SiteRecord, collective_signature, graph_rule_names,
    load_contracts, missing_canonical, run_graph, write_contracts)
from tools.mxtpu_lint.graphcheck.rules import (  # noqa: E402
    CANONICAL_SITES, SPMD_SITES, iter_eqns)

MIB = 1 << 20


# ---------------------------------------------------------------------------
# duck-typed jaxpr stubs (rules only touch .eqns/.primitive.name/.aval)
# ---------------------------------------------------------------------------

class Aval:
    def __init__(self, dtype, shape=()):
        self.dtype = dtype
        self.shape = tuple(shape)


class Var:
    def __init__(self, dtype, shape=()):
        self.aval = Aval(dtype, shape)


class Prim:
    def __init__(self, name):
        self.name = name


class Eqn:
    def __init__(self, prim, invars=(), outvars=(), params=None):
        self.primitive = Prim(prim)
        self.invars = list(invars)
        self.outvars = list(outvars)
        self.params = dict(params or {})


class Jaxpr:
    def __init__(self, eqns, consts=()):
        self.eqns = list(eqns)
        self.consts = list(consts)


class Closed:
    """ClosedJaxpr shape: eqns live one level down at .jaxpr.eqns."""

    def __init__(self, jaxpr):
        self.jaxpr = jaxpr


def psum(shape=(195,), dtype="float32", axes=("dp",)):
    return Eqn("psum", invars=[Var(dtype, shape)],
               outvars=[Var(dtype, shape)], params={"axes": axes})


def graph(records, rules=None, contracts_path=None, **kw):
    kw.setdefault("const_bytes", MIB)
    findings, gctx = run_graph(ROOT, records, rules=rules,
                               contracts_path=contracts_path, **kw)
    return findings, gctx


# ---------------------------------------------------------------------------
# jaxpr walking + signatures
# ---------------------------------------------------------------------------

def test_iter_eqns_descends_into_params_subjaxprs():
    inner = Jaxpr([psum()])
    outer = Closed(Jaxpr([
        Eqn("dot_general"),
        Eqn("shard_map", params={"jaxpr": Closed(inner)}),
    ]))
    names = [e.primitive.name for e in iter_eqns(outer)]
    assert names == ["dot_general", "shard_map", "psum"]


def test_collective_signature_format_and_order():
    j = Jaxpr([
        Eqn("dot_general"),  # non-collective: excluded
        psum(shape=(), dtype="float32"),
        Eqn("all_gather", invars=[Var("bfloat16", (4, 8))],
            params={"axis_name": "dp"}),
    ])
    assert collective_signature(j) == [
        "psum[dp] float32[()]", "all_gather[dp] bfloat16[4x8]"]


def test_missing_canonical():
    assert missing_canonical([]) != []
    full = list(CANONICAL_SITES) + [
        "cachedop_fwd[n:1]", "cachedop_bwd[n:1]", "serving[s:8]", "op[x]",
        "decode_prefill[m:8]"]
    assert missing_canonical(full) == []
    assert "spmd_step" in missing_canonical(
        [s for s in full if s != "spmd_step"])
    assert "serving[...]" in missing_canonical(
        [s for s in full if not s.startswith("serving[")])


def test_graph_rule_catalog():
    assert graph_rule_names() == [
        "amp-dtype-leak", "baked-constant", "collective-order",
        "donation-dead", "host-callback-in-graph"]


# ---------------------------------------------------------------------------
# donation-dead
# ---------------------------------------------------------------------------

def test_donation_dead_fires_on_zero_alias():
    rec = SiteRecord("trainer_fused", jaxpr=Jaxpr([]), donated=True,
                     alias_bytes=0)
    findings, _ = graph([rec], rules=["donation-dead"])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "donation-dead" and f.file == "graph:trainer_fused"
    assert "donation is dead" in f.message


def test_donation_dead_quiet_twins():
    quiet = [
        SiteRecord("a", jaxpr=Jaxpr([]), donated=True, alias_bytes=1560),
        SiteRecord("b", jaxpr=Jaxpr([]), donated=True, alias_bytes=None),
        SiteRecord("c", jaxpr=Jaxpr([]), donated=False, alias_bytes=0),
    ]
    findings, _ = graph(quiet, rules=["donation-dead"])
    assert findings == []


# ---------------------------------------------------------------------------
# amp-dtype-leak
# ---------------------------------------------------------------------------

def _amp_rec(eqns, amp="bfloat16", site="trainer_fused"):
    return SiteRecord(site, jaxpr=Jaxpr(eqns), amp_dtype=amp)


def test_amp_leak_fires_on_f32_matmul_under_policy():
    eqn = Eqn("dot_general",
              invars=[Var("float32", (4, 8)), Var("float32", (8, 2))],
              outvars=[Var("float32", (4, 2))])
    findings, _ = graph([_amp_rec([eqn])], rules=["amp-dtype-leak"])
    assert len(findings) == 1
    assert "escaped low precision" in findings[0].message


def test_amp_leak_fires_on_low_precision_transcendental():
    eqn = Eqn("exp", invars=[Var("bfloat16", (8,))],
              outvars=[Var("bfloat16", (8,))])
    findings, _ = graph([_amp_rec([eqn])], rules=["amp-dtype-leak"])
    assert len(findings) == 1
    assert "PR-5 underflow class" in findings[0].message


def test_amp_leak_quiet_twins():
    mixed_matmul = Eqn(
        "dot_general",
        invars=[Var("bfloat16", (4, 8)), Var("bfloat16", (8, 2))],
        outvars=[Var("float32", (4, 2))])  # f32 accum output is the contract
    f32_exp = Eqn("exp", invars=[Var("float32", (8,))],
                  outvars=[Var("float32", (8,))])
    findings, _ = graph([_amp_rec([mixed_matmul, f32_exp])],
                        rules=["amp-dtype-leak"])
    assert findings == []
    # no active cast policy: everything-f32 is the NORMAL state
    f32_matmul = Eqn("dot_general",
                     invars=[Var("float32", (4, 8)), Var("float32", (8, 2))],
                     outvars=[Var("float32", (4, 2))])
    findings, _ = graph([_amp_rec([f32_matmul], amp=None)],
                        rules=["amp-dtype-leak"])
    assert findings == []


# ---------------------------------------------------------------------------
# baked-constant (+ the graph_meta sanction path)
# ---------------------------------------------------------------------------

def _const(nbytes, shape=(512, 512), dtype="float32"):
    return {"index": 0, "shape": shape, "dtype": dtype, "nbytes": nbytes}


def test_baked_constant_threshold():
    big = SiteRecord("s", jaxpr=Jaxpr([]), consts=[_const(MIB + 1)])
    small = SiteRecord("t", jaxpr=Jaxpr([]), consts=[_const(MIB)])
    findings, _ = graph([big, small], rules=["baked-constant"])
    assert [f.file for f in findings] == ["graph:s"]
    assert "float32[512x512]" in findings[0].message
    # a tighter explicit threshold catches the small one too
    findings, _ = graph([small], rules=["baked-constant"], const_bytes=8)
    assert len(findings) == 1


def test_baked_constant_site_sanction():
    """graph_meta={'disable': ...} at the registration call site (the
    QuantizedNet mechanism) suppresses by SITE, rule-scoped."""
    rec = SiteRecord("serving[int8:8]", jaxpr=Jaxpr([]),
                     consts=[_const(4 * MIB)],
                     donated=True, alias_bytes=0,
                     meta={"disable": ("baked-constant",),
                           "reason": "calibrated int8 payloads"})
    findings, _ = graph([rec], rules=["baked-constant", "donation-dead"])
    # baked-constant sanctioned off; donation-dead still fires
    assert [f.rule for f in findings] == ["donation-dead"]


def test_const_threshold_env_override(monkeypatch):
    from tools.mxtpu_lint.graphcheck.runner import const_threshold
    monkeypatch.setenv("MXTPU_GRAPHCHECK_CONST_BYTES", "4096")
    assert const_threshold() == 4096


# ---------------------------------------------------------------------------
# host-callback-in-graph
# ---------------------------------------------------------------------------

def test_host_callback_fires_once_per_prim_and_sees_subjaxprs():
    inner = Jaxpr([Eqn("io_callback")])
    j = Jaxpr([
        Eqn("pure_callback"),
        Eqn("pure_callback"),  # deduped: one finding per prim name
        Eqn("scan", params={"jaxpr": Closed(inner)}),
    ])
    findings, _ = graph([SiteRecord("s", jaxpr=j)],
                        rules=["host-callback-in-graph"])
    assert sorted(f.message.split("`")[1] for f in findings) == [
        "io_callback", "pure_callback"]


def test_host_callback_quiet_twin():
    j = Jaxpr([Eqn("dot_general"), psum()])
    findings, _ = graph([SiteRecord("s", jaxpr=j)],
                        rules=["host-callback-in-graph"])
    assert findings == []


# ---------------------------------------------------------------------------
# collective-order
# ---------------------------------------------------------------------------

def _pin(tmp_path, sites):
    p = tmp_path / "contracts.json"
    p.write_text(json.dumps({"version": 1, "sites": sites}))
    return str(p)


def test_collective_order_registration_disagreement(tmp_path):
    a = SiteRecord("spmd_step", jaxpr=Jaxpr([psum()]))
    b = SiteRecord("spmd_step", jaxpr=Jaxpr([psum(shape=(7,))]))
    path = _pin(tmp_path, {"spmd_step": ["psum[dp] float32[195]"]})
    findings, _ = graph([a, b], rules=["collective-order"],
                        contracts_path=path)
    assert any("disagree" in f.message for f in findings)


def test_collective_order_unpinned_site(tmp_path):
    rec = SiteRecord("kv_bucket", jaxpr=Jaxpr([psum()]))
    findings, _ = graph([rec], rules=["collective-order"],
                        contracts_path=_pin(tmp_path, {}))
    assert len(findings) == 1
    assert "not pinned" in findings[0].message


def test_collective_order_mismatch_diff(tmp_path):
    rec = SiteRecord("spmd_step", jaxpr=Jaxpr(
        [psum(shape=()), psum(shape=(7,))]))
    path = _pin(tmp_path, {"spmd_step": ["psum[dp] float32[()]",
                                         "psum[dp] float32[195]"]})
    findings, _ = graph([rec], rules=["collective-order"],
                        contracts_path=path)
    assert len(findings) == 1
    msg = findings[0].message
    assert "position 1" in msg
    assert "psum[dp] float32[195]" in msg and "psum[dp] float32[7]" in msg


def test_collective_order_stale_pin(tmp_path):
    rec = SiteRecord("spmd_step", jaxpr=Jaxpr([psum()]))
    path = _pin(tmp_path, {"spmd_step": ["psum[dp] float32[195]"],
                           "ghost_site": ["psum[dp] float32[1]"]})
    findings, _ = graph([rec], rules=["collective-order"],
                        contracts_path=path)
    assert [f.file for f in findings] == ["graph:ghost_site"]
    assert "stale" in findings[0].message


def test_collective_order_clean_match(tmp_path):
    recs = [SiteRecord("spmd_step", jaxpr=Jaxpr([psum()])),
            SiteRecord("spmd_step", jaxpr=Jaxpr([psum()]))]
    path = _pin(tmp_path, {"spmd_step": ["psum[dp] float32[195]"]})
    findings, gctx = graph(recs, rules=["collective-order"],
                           contracts_path=path)
    assert findings == []
    assert gctx.signatures == {"spmd_step": ["psum[dp] float32[195]"]}


# ---------------------------------------------------------------------------
# shared-engine integration: baseline identity, --rule across legs
# ---------------------------------------------------------------------------

def test_graph_finding_baseline_identity_survives_reregistration(tmp_path):
    """A graph finding freezes by (graph:<site>, rule, message) — a later
    harness run re-registering the SAME site (fresh record objects, same
    defect) stays frozen."""
    mk = lambda: SiteRecord("trainer_fused", jaxpr=Jaxpr([]),  # noqa: E731
                            donated=True, alias_bytes=0)
    findings, _ = graph([mk()], rules=["donation-dead"])
    baseline = tmp_path / "b.json"
    entries = write_baseline(str(baseline), findings)
    findings2, _ = graph([mk()], rules=["donation-dead"])
    new, frozen, stale = apply_baseline(findings2, entries)
    assert new == [] and len(frozen) == 1 and stale == []


def test_rule_filter_spans_both_legs():
    """One --rule list mixing AST and graph names: the graph runner
    ignores AST names instead of erroring, and filters to the graph
    names given."""
    rec = SiteRecord("s", jaxpr=Jaxpr([Eqn("pure_callback")]),
                     donated=True, alias_bytes=0)
    findings, _ = graph([rec],
                        rules=["thread-guard", "host-callback-in-graph"])
    assert [f.rule for f in findings] == ["host-callback-in-graph"]


# ---------------------------------------------------------------------------
# pinned contracts file: present, complete, byte-stable
# ---------------------------------------------------------------------------

def test_shipped_contracts_pin_spmd_sites_and_are_stable(tmp_path):
    path = os.path.join(ROOT, CONTRACTS_RELPATH)
    data = load_contracts(path)
    assert data is not None and data.get("version") == 1
    sites = data["sites"]
    assert {"spmd_step", "spmd_superstep", "kv_bucket"} <= set(sites)
    assert set(sites) <= set(SPMD_SITES) | {
        s for s in sites if sites[s]}  # only SPMD or non-empty sigs pinned
    # regeneration from its own payload is byte-identical
    out = tmp_path / "regen.json"
    write_contracts(str(out), sites)
    with open(path, "rb") as f:
        assert out.read_bytes() == f.read()


# ---------------------------------------------------------------------------
# CLI guards (no jax needed)
# ---------------------------------------------------------------------------

def test_cli_update_contracts_requires_graph(capsys):
    assert lint_main(["--update-contracts"]) == 2


def test_cli_graph_rejects_path_args():
    assert lint_main(["--graph", "some_file.py", "--root", ROOT]) == 2


# ---------------------------------------------------------------------------
# the integration gate: real trace harness, real contracts, rc 0
# ---------------------------------------------------------------------------

def test_graph_cli_clean_and_canonical_sites_covered():
    """The shipped tree traces clean under --graph with an EMPTY
    baseline, and the harness registered every canonical site family —
    reverting a dogfood fix or reordering a collective flips rc to 1."""
    res = subprocess.run(
        [sys.executable, "-m", "tools.mxtpu_lint", "--graph", "--json",
         "--root", ROOT],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, (
        f"--graph found NEW findings:\n{res.stdout}\n{res.stderr}")
    out = json.loads(res.stdout)
    assert out["new"] == []
    assert out["rules"] == graph_rule_names()
    missing = missing_canonical(out["sites"])
    assert missing == [], (
        f"trace harness silently skipped site(s) {missing}; "
        f"registered: {out['sites']}")


_HARNESS_RUNNER = """
import json, sys
sys.path.insert(0, {root!r})
from tools.mxtpu_lint.graphcheck.harness import collect_records
records, sites = collect_records()
print("SITES=" + json.dumps(sites))
"""


# canonical-site coverage is certified every tier-1 run by
# test_graph_cli_clean_and_canonical_sites_covered (the real CLI);
# this harness twin compiles the same sites again
@pytest.mark.slow
def test_graphcheck_harness_covers_canonical_sites():
    """The --graph trace harness must register AT LEAST the canonical
    compiled-site set (trainer_fused, superstep, spmd_step/superstep,
    kv_bucket, plus one of each prefixed family) — a silently-skipped
    harness leg would otherwise let the graph gate fake green."""
    res = subprocess.run(
        [sys.executable, "-c", _HARNESS_RUNNER.format(root=ROOT)],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("SITES=")]
    assert line, res.stdout[-2000:]
    sites = json.loads(line[0][len("SITES="):])
    missing = missing_canonical(sites)
    assert missing == [], (missing, sites)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
