"""Static-analysis gate: every rule id against a known-bad fixture
(exact finding ids + line numbers), the committed-baseline round trip,
and the repo-walk clean check CI relies on.

The fixtures live in ``tests/fixtures/analysis/`` — one file (or role
pair, for the cross-file contract rules) per rule.  Each case runs the
engine with EXPLICIT paths, which bypasses targeting globs and runs
every rule, so the expected set doubles as a no-false-positive check:
any other rule firing on the fixture fails the exact-set assertion.
"""
import json
from pathlib import Path

import pytest

from repro.analysis import AnalysisEngine, Baseline, default_rules
from repro.analysis.__main__ import main as cli_main

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "fixtures" / "analysis"

# rule id under test, fixture files, exact expected {(rule, line), ...}
CASES = [
    ("JAX101", ["jax101.py"], {("JAX101", 7)}),
    ("JAX102", ["jax102.py"], {("JAX102", 8), ("JAX102", 9)}),
    ("JAX103", ["jax103.py"], {("JAX103", 7)}),
    ("JAX104", ["jax104.py"], {("JAX104", 7)}),
    ("JAX105", ["jax105.py"], {("JAX105", 5), ("JAX105", 9)}),
    ("JAX106", ["jax106.py"], {("JAX106", 6)}),
    ("ASY201", ["asy201.py"], {("ASY201", 5), ("ASY201", 6)}),
    ("ASY202", ["asy202.py"], {("ASY202", 7)}),
    ("ASY203", ["asy203.py"], {("ASY203", 2)}),
    ("ASY204", ["asy204.py"], {("ASY204", 11)}),
    ("ASY205", ["asy205.py"], {("ASY205", 7), ("ASY205", 8)}),
    # the span around pump() closes before the await in pump_ok: silent
    ("ASY206", ["asy206.py"], {("ASY206", 5)}),
    # wire contract rules need both roles in the file set; the findings
    # anchor in the consumer (client) file
    ("CON301", ["wire_client.py", "wire_server.py"],
     {("CON301", 3), ("CON302", 7)}),
    ("CON302", ["wire_client.py", "wire_server.py"],
     {("CON301", 3), ("CON302", 7)}),
    ("CON303", ["tel_gateway.py", "tel_prometheus.py"], {("CON303", 5)}),
    ("CON304", ["con304.py"], {("CON304", 4), ("CON304", 8)}),
    ("ENGINE000", ["broken.py"], {("ENGINE000", 1)}),
]


@pytest.mark.parametrize("rule_id,files,expected", CASES,
                         ids=[c[0] for c in CASES])
def test_rule_fires_at_exact_lines(rule_id, files, expected):
    engine = AnalysisEngine(ROOT)
    findings = engine.run([FIXTURES / f for f in files])
    got = {(f.rule_id, f.line) for f in findings}
    assert got == expected
    assert any(f.rule_id == rule_id for f in findings)


def test_every_registered_rule_has_a_fixture_case():
    file_rules, repo_rules = default_rules()
    registered = {r.id for r in file_rules} | {r.id for r in repo_rules}
    covered = {rid for _, _, expected in CASES for rid, _ in expected}
    assert registered <= covered, (
        f"rules without a fixture case: {sorted(registered - covered)}"
    )


def test_one_sided_contract_fixture_is_silent():
    # a lone client (or emitter) with no counterpart present must not
    # misfire — the repo rules need both roles to diff
    engine = AnalysisEngine(ROOT)
    findings = engine.run([FIXTURES / "wire_client.py"])
    assert findings == []


def test_known_good_patterns_are_clean(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(
        "import asyncio\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n\n"
        "@jax.jit\n"
        "def f(x, n: int = 2):\n"
        "    if n > 1:\n"          # branch on an int-annotated config arg
        "        return jnp.abs(x)\n"
        "    return x\n"
        "\n\n"
        "async def pump(q):\n"
        "    async with q.lock:\n"  # asyncio lock across await is fine
        "        await q.flush()\n"
        "    await asyncio.sleep(0.1)\n"
    )
    engine = AnalysisEngine(ROOT)
    assert engine.run([good]) == []


def test_repo_walk_is_clean_against_committed_baseline():
    """The exact check the CI lint job performs."""
    engine = AnalysisEngine(ROOT)
    findings = engine.run()
    baseline = Baseline.load(ROOT / "analysis" / "baseline.json")
    new, suppressed, stale = baseline.split(findings)
    assert new == [], "\n".join(f.render() for f in new)
    assert stale == [], f"stale baseline entries: {stale}"
    # zero-silent-suppression invariant: every suppression carries a
    # non-placeholder reason
    for entry in baseline.entries.values():
        assert entry["reason"]
        assert not entry["reason"].startswith("unreviewed")


def test_fingerprint_survives_line_drift(tmp_path):
    mod = tmp_path / "mod.py"
    body = "import time\n\n\nasync def f():\n    time.sleep(1)\n"
    engine = AnalysisEngine(ROOT)
    mod.write_text(body)
    first = engine.run([mod])
    mod.write_text("# moved\n# down\n" + body)
    second = engine.run([mod])
    assert [f.fingerprint for f in first] == [f.fingerprint for f in second]
    assert [f.line + 2 for f in first] == [f.line for f in second]


def test_baseline_requires_a_reason(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({
        "version": 1,
        "entries": [{"fingerprint": "a" * 16, "rule": "ASY201",
                     "path": "x.py", "line": 1, "snippet": "s",
                     "reason": ""}],
    }))
    with pytest.raises(ValueError, match="reason"):
        Baseline.load(path)


def test_baseline_rejects_unknown_version(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError, match="version"):
        Baseline.load(path)


# -- CLI -------------------------------------------------------------------


def _write_bad(path: Path) -> None:
    path.write_text("import time\n\n\nasync def f():\n    time.sleep(1)\n")


def test_cli_baseline_round_trip(tmp_path, capsys):
    bad = tmp_path / "bad_mod.py"
    _write_bad(bad)
    baseline = tmp_path / "baseline.json"
    common = [str(bad), "--root", str(ROOT), "--baseline", str(baseline)]

    assert cli_main(common) == 1                       # finding, no baseline
    assert cli_main(common + ["--update-baseline"]) == 0
    assert baseline.exists()
    capsys.readouterr()

    assert cli_main(common) == 0                       # baselined -> clean
    out = capsys.readouterr().out
    assert "ASY201" in out and "baselined" in out

    # the gate stays a gate: a NEW non-baselined finding still fails
    worse = tmp_path / "worse_mod.py"
    worse.write_text("def kick(loop, coro):\n    loop.create_task(coro)\n")
    rc = cli_main([str(bad), str(worse), "--root", str(ROOT),
                   "--baseline", str(baseline)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "ASY203" in out and "worse_mod.py:2" in out


def test_cli_json_format_and_report(tmp_path, capsys):
    bad = tmp_path / "bad_mod.py"
    _write_bad(bad)
    report = tmp_path / "report.json"
    rc = cli_main([str(bad), "--root", str(ROOT), "--baseline", "",
                   "--format", "json", "--report", str(report)])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload == json.loads(report.read_text())
    assert payload["ok"] is False
    (finding,) = payload["findings"]
    assert finding["rule"] == "ASY201"
    assert finding["line"] == 5
    assert finding["fingerprint"]


def test_cli_update_keeps_reviewed_reasons(tmp_path, capsys):
    bad = tmp_path / "bad_mod.py"
    _write_bad(bad)
    baseline_path = tmp_path / "baseline.json"
    common = [str(bad), "--root", str(ROOT),
              "--baseline", str(baseline_path)]
    cli_main(common + ["--update-baseline"])
    data = json.loads(baseline_path.read_text())
    data["entries"][0]["reason"] = "reviewed: fixture sleeps on purpose"
    baseline_path.write_text(json.dumps(data))
    capsys.readouterr()

    cli_main(common + ["--update-baseline"])           # re-run keeps reason
    data = json.loads(baseline_path.read_text())
    assert data["entries"][0]["reason"] == \
        "reviewed: fixture sleeps on purpose"


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("JAX101", "ASY204", "CON303", "CON304"):
        assert rule_id in out
