"""fedlint framework + pass tests.

Fixture files live in ``tests/lint_fixtures/`` (non-``test_`` names so
pytest never collects them; they are parsed, never imported). Each bad
fixture marks the expected findings with ``# SEED: <rule>`` comments on
the exact line the finding must anchor to; clean counterparts must lint
to zero findings. Fixtures are loaded under a ``fixtures/`` pseudo-path
so test-path-sensitive rules (``pallas-interpret-hardcoded``) behave as
they do for ``src/``.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.lint import (available_passes, findings_to_json, jaxprs,
                        rule_catalogue, run_lint, wire_checks)
from repro.lint.core import (Finding, LintPass, Module, is_test_path,
                             make_passes, run_passes)

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"

BAD_FIXTURES = ["host_sync_bad.py", "vjp_bad.py", "mesh_bad.py",
                "pallas_bad.py", "wire_bad.py"]
CLEAN_FIXTURES = ["host_sync_clean.py", "vjp_clean.py", "mesh_clean.py",
                  "pallas_clean.py", "wire_clean.py"]

_SEED_RE = re.compile(r"#\s*SEED:\s*(?P<rules>[a-z0-9,\- ]+)$")


def _load(name: str) -> Module:
    # a fixtures/ pseudo-path so is_test_path() is False, as for src/
    return Module(f"fixtures/{name}", (FIXTURES / name).read_text())


def _seeds(source: str):
    out = []
    for lineno, line in enumerate(source.splitlines(), start=1):
        m = _SEED_RE.search(line)
        if m:
            out.extend((r.strip(), lineno)
                       for r in m.group("rules").split(","))
    return sorted(out)


@pytest.fixture
def tmp_manifest(tmp_path, monkeypatch):
    """Point the wire manifest at a scratch file (empty until pinned)."""
    path = tmp_path / "wire_manifest.json"
    monkeypatch.setattr(wire_checks, "MANIFEST_PATH", path)
    return path


# ---------------------------------------------------------------------------
# seeded violations / clean baselines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", BAD_FIXTURES)
def test_seeded_violations_found_at_marked_lines(name, tmp_manifest):
    mod = _load(name)
    expected = _seeds(mod.source)
    assert expected, f"{name} has no SEED markers"
    got = sorted({(f.rule, f.line)
                  for f in run_passes([mod], make_passes())})
    assert got == expected


@pytest.mark.parametrize("name", CLEAN_FIXTURES)
def test_clean_fixtures_have_zero_findings(name, tmp_manifest):
    if name == "wire_clean.py":
        wire_checks.update_manifest([str(FIXTURES / name)])
    findings = run_passes([_load(name)], make_passes())
    assert findings == []


def test_every_pass_is_exercised_by_a_fixture(tmp_manifest):
    hit = set()
    for name in BAD_FIXTURES:
        for f in run_passes([_load(name)], make_passes()):
            hit.add(f.pass_name)
    for name in ("fleet_loops_bad.py", "wire_decode_bad.py",
                 "obs_events_bad.py"):
        for f in run_passes([_load_federated(name)], make_passes()):
            hit.add(f.pass_name)
    assert hit == set(available_passes())


# ---------------------------------------------------------------------------
# fleet-scale pass: path-gated to repro/federated/ hot paths
# ---------------------------------------------------------------------------

def _load_federated(name: str) -> Module:
    """The fleet-scale pass only fires inside ``repro/federated/`` non-test
    paths, so its fixtures load under a federated pseudo-path instead of
    the standard ``fixtures/`` one."""
    return Module(f"src/repro/federated/{name}",
                  (FIXTURES / name).read_text())


def test_fleet_loop_seeded_violations(tmp_manifest):
    mod = _load_federated("fleet_loops_bad.py")
    expected = _seeds(mod.source)
    assert expected, "fleet_loops_bad.py has no SEED markers"
    got = sorted({(f.rule, f.line)
                  for f in run_passes([mod], make_passes())})
    assert got == expected


def test_fleet_loop_clean_fixture(tmp_manifest):
    """Vectorized idiom, cohort-sized loops and a reviewed suppression all
    lint clean under the hot-path pseudo-path."""
    findings = run_passes([_load_federated("fleet_loops_clean.py")],
                          make_passes())
    assert findings == []


def test_fleet_loop_pass_is_path_gated(tmp_manifest):
    src = (FIXTURES / "fleet_loops_bad.py").read_text()
    # outside repro/federated/: not a hot path, nothing fires
    assert run_passes([Module("fixtures/fleet_loops_bad.py", src)],
                      make_passes(["fleet-scale"])) == []
    # federated test files are exempt too
    assert run_passes([Module("src/repro/federated/test_x.py", src)],
                      make_passes(["fleet-scale"])) == []


# ---------------------------------------------------------------------------
# obs-events pass: emitted names vs the schema registry
# ---------------------------------------------------------------------------

def test_obs_event_seeded_violations(tmp_manifest):
    """An unregistered literal name and a computed name both fire at the
    marked lines."""
    mod = _load_federated("obs_events_bad.py")
    expected = _seeds(mod.source)
    assert expected, "obs_events_bad.py has no SEED markers"
    got = sorted({(f.rule, f.line)
                  for f in run_passes([mod], make_passes())})
    assert got == expected


def test_obs_event_clean_fixture(tmp_manifest):
    """Registered names, a reviewed dynamic-name suppression, and a
    non-obs call with an event-looking string all lint clean."""
    findings = run_passes([_load_federated("obs_events_clean.py")],
                          make_passes())
    assert findings == []


def test_obs_event_pass_is_path_gated(tmp_manifest):
    src = (FIXTURES / "obs_events_bad.py").read_text()
    # outside repro/federated/: emitters there are the obs layer's own
    assert run_passes([Module("fixtures/obs_events_bad.py", src)],
                      make_passes(["obs-events"])) == []
    assert run_passes([Module("src/repro/federated/test_x.py", src)],
                      make_passes(["obs-events"])) == []


def test_every_registered_federated_emission_is_in_schema():
    """The live check the CI gate runs: every obs.event in the shipped
    federated layer names a registered event."""
    mods = []
    fed = REPO_ROOT / "src" / "repro" / "federated"
    for path in sorted(fed.glob("*.py")):
        mods.append(Module(str(path), path.read_text()))
    assert run_passes(mods, make_passes(["obs-events"])) == []


# ---------------------------------------------------------------------------
# wire-decode pass: unguarded decodes in hot paths
# ---------------------------------------------------------------------------

def test_wire_decode_seeded_violations(tmp_manifest):
    """Bare decode, wrong-hierarchy except, and a decode inside a handler
    body (outside its own try) all fire at the marked lines."""
    mod = _load_federated("wire_decode_bad.py")
    expected = _seeds(mod.source)
    assert expected, "wire_decode_bad.py has no SEED markers"
    got = sorted({(f.rule, f.line)
                  for f in run_passes([mod], make_passes())})
    assert got == expected


def test_wire_decode_clean_fixture(tmp_manifest):
    """Typed-hierarchy catches (incl. tuple form and the ValueError base)
    and a reviewed loopback suppression all lint clean."""
    findings = run_passes([_load_federated("wire_decode_clean.py")],
                          make_passes())
    assert findings == []


def test_wire_decode_pass_is_path_gated(tmp_manifest):
    src = (FIXTURES / "wire_decode_bad.py").read_text()
    # outside repro/federated/: not a hot path, nothing fires
    assert run_passes([Module("fixtures/wire_decode_bad.py", src)],
                      make_passes(["wire-decode"])) == []
    # federated test files are exempt
    assert run_passes([Module("src/repro/federated/test_x.py", src)],
                      make_passes(["wire-decode"])) == []
    # the codec module itself is exempt: it *produces* the hierarchy
    assert run_passes([Module("src/repro/federated/wire.py", src)],
                      make_passes(["wire-decode"])) == []


def test_wire_decode_repo_tree_is_clean():
    """Every decode call in the real federated package is guarded (or
    carries a reviewed loopback suppression)."""
    findings = run_lint([str(REPO_ROOT / "src" / "repro" / "federated")],
                        ["wire-decode"])
    assert findings == [], [f.format() for f in findings]


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_line_suppression_silences_the_rule(tmp_manifest):
    findings = run_passes([_load("host_sync_suppressed.py")], make_passes())
    assert findings == []


def test_without_suppression_the_same_code_is_flagged(tmp_manifest):
    src = (FIXTURES / "host_sync_suppressed.py").read_text()
    stripped = src.replace("  # fedlint: disable=host-sync-in-jit", "")
    assert stripped != src
    findings = run_passes([Module("fixtures/host_sync_suppressed.py",
                                  stripped)], make_passes())
    assert [f.rule for f in findings] == ["host-sync-in-jit"]


def test_file_suppression_and_disable_all(tmp_manifest):
    src = (FIXTURES / "host_sync_suppressed.py").read_text()
    for comment in ("# fedlint: disable-file=host-sync-in-jit",
                    "# fedlint: disable-file=all"):
        body = src.replace("# fedlint: disable=host-sync-in-jit", "") \
            + f"\n{comment}\n"
        findings = run_passes([Module("fixtures/x.py", body)], make_passes())
        assert findings == [], comment


# ---------------------------------------------------------------------------
# framework: registry, findings, JSON schema
# ---------------------------------------------------------------------------

def test_registry_lists_the_eight_passes():
    assert available_passes() == ("custom-vjp", "fleet-scale", "host-sync",
                                  "mesh-axes", "obs-events", "pallas",
                                  "wire-decode", "wire-format")


def test_unknown_pass_selection_fails_loudly():
    with pytest.raises(ValueError, match="registered"):
        make_passes(["no-such-pass"])


def test_rule_catalogue_covers_every_pass():
    cat = rule_catalogue()
    assert set(cat) == set(available_passes())
    assert all(rules for rules in cat.values())


def test_unregistered_rule_emission_is_an_error():
    class P(LintPass):
        name = "p"
        rules = {"known": "desc"}
    mod = Module("x.py", "pass\n")
    with pytest.raises(ValueError, match="unregistered"):
        P().finding(mod, 1, "unknown", "msg")


def test_finding_severity_is_validated():
    with pytest.raises(ValueError):
        Finding(path="x.py", line=1, rule="r", message="m", severity="fatal")


def test_is_test_path():
    assert is_test_path("tests/test_foo.py")
    assert is_test_path("pkg/test_bar.py")
    assert not is_test_path("src/repro/kernels/ops.py")


def test_json_schema_is_stable(tmp_manifest):
    findings = run_passes([_load("vjp_bad.py")], make_passes())
    doc = json.loads(findings_to_json(findings))
    assert doc["schema_version"] == 1
    assert set(doc) == {"schema_version", "findings", "counts", "total"}
    assert doc["total"] == len(findings) == len(doc["findings"])
    for entry in doc["findings"]:
        assert set(entry) == {"path", "line", "rule", "severity", "pass",
                              "message"}
    assert sum(doc["counts"].values()) == doc["total"]


def test_select_runs_only_that_pass(tmp_manifest):
    findings = run_passes([_load("vjp_bad.py")], make_passes(["host-sync"]))
    assert findings == []
    findings = run_passes([_load("vjp_bad.py")], make_passes(["custom-vjp"]))
    assert findings and all(f.pass_name == "custom-vjp" for f in findings)


# ---------------------------------------------------------------------------
# wire manifest: version-stale detection
# ---------------------------------------------------------------------------

def test_wire_body_edit_without_version_bump_is_stale(tmp_manifest):
    src = (FIXTURES / "wire_clean.py").read_text()
    wire_checks.update_manifest([str(FIXTURES / "wire_clean.py")])
    edited = src.replace("len(payload)) + payload",
                         "len(payload) + 1) + payload")
    assert edited != src
    findings = run_passes([Module("fixtures/wire_clean.py", edited)],
                          make_passes(["wire-format"]))
    stale = [f for f in findings if f.rule == "wire-version-stale"]
    assert len(stale) == 2
    assert all("bump the version" in f.message for f in stale)


def test_wire_docstring_edit_does_not_change_the_hash(tmp_manifest):
    src = (FIXTURES / "wire_clean.py").read_text()
    wire_checks.update_manifest([str(FIXTURES / "wire_clean.py")])
    edited = src.replace(
        "def encode_dense(payload):\n",
        'def encode_dense(payload):\n    """v1 wire header."""\n')
    assert edited != src
    findings = run_passes([Module("fixtures/wire_clean.py", edited)],
                          make_passes(["wire-format"]))
    assert findings == []


def test_repo_wire_manifest_is_current():
    """The checked-in manifest must match the checked-in encoders — a
    drifted manifest means someone edited wire.py without refreshing."""
    findings = run_lint([str(REPO_ROOT / "src" / "repro" / "federated"
                             / "wire.py")], ["wire-format"])
    assert findings == [], [f.format() for f in findings]


# ---------------------------------------------------------------------------
# jaxpr-level helpers
# ---------------------------------------------------------------------------

def test_collective_axis_names_recurses_into_subjaxprs():
    def f(x):
        return jax.jit(lambda y: jax.lax.psum(y, "data"))(x)
    axes = jaxprs.collective_axis_names(f, jnp.ones(4),
                                        axis_env=[("data", 2)])
    assert axes == {"data"}


def test_undeclared_collective_axes_clean():
    def f(x):
        return x * 2.0
    assert jaxprs.undeclared_collective_axes(f, ["data"], jnp.ones(3)) \
        == set()


def test_host_callback_primitives_detected():
    def g(x):
        jax.debug.print("x = {x}", x=x)
        return x
    # jax.debug.print lowers to ``debug_print`` on JAX >= 0.8 (before:
    # ``debug_callback``); either way it is a host callback
    found = jaxprs.host_callback_primitives(g, jnp.ones(3))
    assert {"debug_print", "debug_callback"} & set(found), found
    def h(x):
        return x + 1.0
    assert jaxprs.host_callback_primitives(h, jnp.ones(3)) == []


def test_integer_cotangents_follow_float0_contract():
    def good(x, i):
        return x * 2.0
    assert jaxprs.integer_cotangent_violations(
        good, jnp.ones(3), jnp.arange(3)) == []


def test_integer_cotangent_check_propagates_bwd_structure_errors():
    @jax.custom_vjp
    def broken(x, i):
        return x

    def broken_fwd(x, i):
        return broken(x, i), None

    def broken_bwd(res, ct):
        return (ct,)   # missing the integer primal's cotangent slot

    broken.defvjp(broken_fwd, broken_bwd)
    with pytest.raises(TypeError):
        jaxprs.integer_cotangent_violations(broken, jnp.ones(3),
                                            jnp.arange(3))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _run_cli(*args):
    env = dict(os.environ, PYTHONPATH=str(REPO_ROOT / "src"))
    return subprocess.run([sys.executable, "-m", "repro.lint", *args],
                          capture_output=True, text=True, env=env,
                          cwd=REPO_ROOT)


def test_cli_exit_codes_and_output():
    bad = str(FIXTURES / "vjp_bad.py")
    r = _run_cli(bad, "--select", "custom-vjp")
    assert r.returncode == 1
    assert "[vjp-missing-defvjp]" in r.stdout

    r = _run_cli(str(FIXTURES / "vjp_clean.py"), "--select", "custom-vjp")
    assert r.returncode == 0

    r = _run_cli(bad, "--select", "custom-vjp", "--json")
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["schema_version"] == 1 and doc["total"] > 0


def test_cli_usage_errors():
    assert _run_cli("no/such/path.py").returncode == 2
    assert _run_cli("--select", "bogus", ".").returncode == 2


def test_cli_list_rules():
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for pass_name in available_passes():
        assert pass_name in r.stdout


# ---------------------------------------------------------------------------
# raw-timing-in-hot-path: ad-hoc timers/print in federated/core hot paths
# ---------------------------------------------------------------------------

_HOT_TIMING_SRC = """\
import time

def round_loop():
    t0 = time.perf_counter()
    print("round took", time.perf_counter() - t0)
"""


def _timing_findings(path, src=_HOT_TIMING_SRC, tmp=None):
    return [f for f in run_passes([Module(path, src)], make_passes())
            if f.rule == "raw-timing-in-hot-path"]


def test_raw_timing_flagged_in_hot_paths(tmp_manifest):
    findings = _timing_findings("src/repro/federated/runtime.py")
    # two perf_counter calls + one print
    assert sorted(f.line for f in findings) == [4, 5, 5]
    assert any("repro.obs.span" in f.message for f in findings)
    assert any("repro.obs.event" in f.message for f in findings)
    assert _timing_findings("src/repro/core/kmeans.py")


def test_raw_timing_exempt_paths(tmp_manifest):
    for path in ("src/repro/obs/spans.py",          # obs implements timing
                 "benchmarks/common.py",            # benchmarks time freely
                 "tests/test_something.py",         # test code
                 "src/repro/federated/test_util.py",
                 "src/repro/models/paper_models.py"):
        assert _timing_findings(path) == [], path


def test_raw_timing_line_suppression(tmp_manifest):
    src = _HOT_TIMING_SRC.replace(
        "t0 = time.perf_counter()",
        "t0 = time.perf_counter()"
        "  # fedlint: disable=raw-timing-in-hot-path")
    findings = _timing_findings("src/repro/federated/runtime.py", src)
    assert sorted(f.line for f in findings) == [5, 5]  # only the bare line
