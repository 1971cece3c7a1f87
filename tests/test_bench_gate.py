"""The CI benchmark gates (check_fleet_regression.py, check_reliability_gate.py).

The fleet gate's contract after the unknown-row fix: row families the
committed reference does not know yet are WARNINGS (new benchmarks land
ahead of their reference refresh), while known rows fail the gate when
they regress past tolerance, go missing, or stop parsing.  The reference
file itself stays strictly parsed — it is curated, so a malformed row
there is a repo bug.  The same known-row machinery gates the cold-start
ratios when --coldstart-fresh/--coldstart-reference are given, plus the
bitexact/fallback status rows which must start with "ok".

The reliability gate (extracted from the old ci.yml heredoc) fails when
any BER=0 sweep point is not bit-exact OR when the sweep has no BER=0
control points at all.
"""

import json

import pytest

from benchmarks import check_fleet_regression as gate
from benchmarks import check_reliability_gate as rel_gate

def _write(tmp_path, fname, rows, status="ok"):
    path = tmp_path / fname
    path.write_text(json.dumps(
        {"module": "fleet", "status": status, "rows": rows, "error": None}))
    return str(path)


def _speedup(name, x):
    return {"name": name, "derived": f"{x:.2f}x vs baseline"}


@pytest.fixture
def reference(tmp_path):
    return _write(tmp_path, "ref.json",
                  [_speedup("fleet.S8.speedup", 4.0)])


def test_gate_passes_within_tolerance(tmp_path, reference):
    fresh = _write(tmp_path, "fresh.json",
                   [_speedup("fleet.S8.speedup", 3.5)])
    assert gate.main([fresh, reference, "--tolerance", "0.25"]) == 0


def test_gate_fails_on_regression(tmp_path, reference):
    fresh = _write(tmp_path, "fresh.json",
                   [_speedup("fleet.S8.speedup", 1.0)])
    assert gate.main([fresh, reference, "--tolerance", "0.25"]) == 1


def test_unknown_row_family_warns_not_crashes(tmp_path, reference, capsys):
    """A fresh run with NEW speedup families (parseable or not) must not
    crash or fail the gate — the reference simply doesn't know them yet."""
    fresh = _write(tmp_path, "fresh.json", [
        _speedup("fleet.S8.speedup", 4.0),
        _speedup("fleet.newfamily.speedup", 9.0),
        {"name": "fleet.weird.speedup", "derived": "not a ratio at all"},
    ])
    assert gate.main([fresh, reference]) == 0
    err = capsys.readouterr().err
    assert "fleet.newfamily.speedup" in err and "skipping" in err
    assert "fleet.weird.speedup" in err


def test_known_row_missing_fails(tmp_path, reference):
    fresh = _write(tmp_path, "fresh.json",
                   [_speedup("fleet.other.speedup", 4.0)])
    assert gate.main([fresh, reference]) == 1


def test_known_row_unparseable_fails(tmp_path, reference):
    fresh = _write(tmp_path, "fresh.json", [
        {"name": "fleet.S8.speedup", "derived": "garbage"},
    ])
    assert gate.main([fresh, reference]) == 1


def test_empty_reference_fails(tmp_path):
    ref = _write(tmp_path, "ref.json", [])
    fresh = _write(tmp_path, "fresh.json",
                   [_speedup("fleet.S8.speedup", 4.0)])
    assert gate.main([fresh, ref]) == 1


def test_reference_stays_strict(tmp_path):
    ref = _write(tmp_path, "ref.json",
                 [{"name": "fleet.S8.speedup", "derived": "corrupt"}])
    fresh = _write(tmp_path, "fresh.json",
                   [_speedup("fleet.S8.speedup", 4.0)])
    with pytest.raises(SystemExit):
        gate.main([fresh, ref])


# -- cold-start gating (--coldstart-fresh / --coldstart-reference) ----------

COLD_STATUS_ROWS = [
    {"name": "coldstart.bitexact", "derived": "ok identical decisions"},
    {"name": "coldstart.fallback", "derived": "ok stale artifact refused"},
]


@pytest.fixture
def fleet_fresh(tmp_path):
    return _write(tmp_path, "fleet_fresh.json",
                  [_speedup("fleet.S8.speedup", 4.0)])


@pytest.fixture
def cold_reference(tmp_path):
    return _write(tmp_path, "cold_ref.json", [
        _speedup("coldstart.S8.warmcache.speedup", 2.0),
        _speedup("coldstart.S8.serialized.speedup", 4.0),
    ])


def _cold_args(fleet_fresh, reference, cold_fresh, cold_reference):
    return [fleet_fresh, reference,
            "--coldstart-fresh", cold_fresh,
            "--coldstart-reference", cold_reference]


def test_coldstart_gate_passes(tmp_path, fleet_fresh, reference,
                               cold_reference):
    cold = _write(tmp_path, "cold.json", [
        _speedup("coldstart.S8.warmcache.speedup", 3.0),
        _speedup("coldstart.S8.serialized.speedup", 6.0),
    ] + COLD_STATUS_ROWS)
    assert gate.main(
        _cold_args(fleet_fresh, reference, cold, cold_reference)) == 0


def test_coldstart_ratio_regression_fails(tmp_path, fleet_fresh, reference,
                                          cold_reference):
    cold = _write(tmp_path, "cold.json", [
        _speedup("coldstart.S8.warmcache.speedup", 2.0),
        _speedup("coldstart.S8.serialized.speedup", 1.1),  # floor is 3.0
    ] + COLD_STATUS_ROWS)
    assert gate.main(
        _cold_args(fleet_fresh, reference, cold, cold_reference)) == 1


def test_coldstart_bitexact_must_say_ok(tmp_path, fleet_fresh, reference,
                                        cold_reference):
    cold = _write(tmp_path, "cold.json", [
        _speedup("coldstart.S8.warmcache.speedup", 3.0),
        _speedup("coldstart.S8.serialized.speedup", 6.0),
        {"name": "coldstart.bitexact", "derived": "MISMATCH between paths"},
        COLD_STATUS_ROWS[1],
    ])
    assert gate.main(
        _cold_args(fleet_fresh, reference, cold, cold_reference)) == 1


def test_coldstart_missing_fallback_row_fails(tmp_path, fleet_fresh,
                                              reference, cold_reference):
    cold = _write(tmp_path, "cold.json", [
        _speedup("coldstart.S8.warmcache.speedup", 3.0),
        _speedup("coldstart.S8.serialized.speedup", 6.0),
        COLD_STATUS_ROWS[0],  # no coldstart.fallback row at all
    ])
    assert gate.main(
        _cold_args(fleet_fresh, reference, cold, cold_reference)) == 1


def test_coldstart_unknown_family_warns(tmp_path, fleet_fresh, reference,
                                        cold_reference, capsys):
    cold = _write(tmp_path, "cold.json", [
        _speedup("coldstart.S8.warmcache.speedup", 3.0),
        _speedup("coldstart.S8.serialized.speedup", 6.0),
        _speedup("coldstart.S64.serialized.speedup", 9.0),  # not in ref
    ] + COLD_STATUS_ROWS)
    assert gate.main(
        _cold_args(fleet_fresh, reference, cold, cold_reference)) == 0
    err = capsys.readouterr().err
    assert "coldstart.S64.serialized.speedup" in err and "skipping" in err


def test_coldstart_args_must_pair(fleet_fresh, reference):
    with pytest.raises(SystemExit):
        gate.main([fleet_fresh, reference, "--coldstart-fresh", "x.json"])


# -- elastic-fleet churn gate (--churn-fresh/--churn-reference) -------------

CHURN_STATUS_ROWS = [
    {"name": "churn.norecompile", "derived": "ok (0 compiles over 36 ops)"},
    {"name": "churn.recovery", "derived": "ok (5 ops replayed bit-exact)"},
]


@pytest.fixture
def churn_reference(tmp_path):
    return _write(tmp_path, "churn_ref.json", [
        _speedup("churn.S8.speedup", 2.0),
        _speedup("churn.S8.retention.speedup", 0.05),
    ])


def _churn_args(fleet_fresh, reference, churn_fresh, churn_reference):
    return [fleet_fresh, reference,
            "--churn-fresh", churn_fresh,
            "--churn-reference", churn_reference]


def test_churn_gate_passes(tmp_path, fleet_fresh, reference,
                           churn_reference):
    churn = _write(tmp_path, "churn.json", [
        _speedup("churn.S8.speedup", 2.5),
        _speedup("churn.S8.retention.speedup", 0.12),
    ] + CHURN_STATUS_ROWS)
    assert gate.main(
        _churn_args(fleet_fresh, reference, churn, churn_reference)) == 0


def test_churn_ratio_regression_fails(tmp_path, fleet_fresh, reference,
                                      churn_reference):
    churn = _write(tmp_path, "churn.json", [
        _speedup("churn.S8.speedup", 1.0),  # floor is 1.5
        _speedup("churn.S8.retention.speedup", 0.12),
    ] + CHURN_STATUS_ROWS)
    assert gate.main(
        _churn_args(fleet_fresh, reference, churn, churn_reference)) == 1


def test_churn_norecompile_must_say_ok(tmp_path, fleet_fresh, reference,
                                       churn_reference):
    churn = _write(tmp_path, "churn.json", [
        _speedup("churn.S8.speedup", 2.5),
        _speedup("churn.S8.retention.speedup", 0.12),
        {"name": "churn.norecompile",
         "derived": "FAILED: region compiled 3 XLA program(s)"},
        CHURN_STATUS_ROWS[1],
    ])
    assert gate.main(
        _churn_args(fleet_fresh, reference, churn, churn_reference)) == 1


def test_churn_missing_recovery_row_fails(tmp_path, fleet_fresh, reference,
                                          churn_reference):
    churn = _write(tmp_path, "churn.json", [
        _speedup("churn.S8.speedup", 2.5),
        _speedup("churn.S8.retention.speedup", 0.12),
        CHURN_STATUS_ROWS[0],  # no churn.recovery row at all
    ])
    assert gate.main(
        _churn_args(fleet_fresh, reference, churn, churn_reference)) == 1


def test_churn_args_must_pair(fleet_fresh, reference):
    with pytest.raises(SystemExit):
        gate.main([fleet_fresh, reference, "--churn-reference", "x.json"])


# -- reliability zero-BER gate (check_reliability_gate.py) ------------------

def _rel_point(ber, bitexact=True, scheme="none"):
    return {"variant": "sparse_opt", "density": 0.25, "scheme": scheme,
            "ber": ber, "zero_ber_bitexact": bitexact}


def _rel_write(tmp_path, points, fname="rel.json"):
    path = tmp_path / fname
    rows = [{"name": f"reliability.p{i}", "point": p}
            for i, p in enumerate(points)]
    rows.append({"name": "reliability.summary", "derived": "no point key"})
    path.write_text(json.dumps(
        {"module": "reliability", "status": "ok", "rows": rows}))
    return str(path)


def test_reliability_gate_passes(tmp_path, capsys):
    path = _rel_write(tmp_path, [
        _rel_point(0.0), _rel_point(0.0, scheme="secded"), _rel_point(0.01)])
    assert rel_gate.main([path]) == 0
    assert "bitexact=True" in capsys.readouterr().out


def test_reliability_gate_fails_on_nonexact_zero_ber(tmp_path):
    path = _rel_write(tmp_path, [
        _rel_point(0.0), _rel_point(0.0, bitexact=False, scheme="parity")])
    assert rel_gate.main([path]) == 1


def test_reliability_gate_fails_without_control_points(tmp_path):
    path = _rel_write(tmp_path, [_rel_point(0.01), _rel_point(0.03)])
    assert rel_gate.main([path]) == 1


def test_reliability_nonzero_points_do_not_gate(tmp_path):
    """Only BER=0 points carry the bit-exactness contract."""
    path = _rel_write(tmp_path, [
        _rel_point(0.0), _rel_point(0.01, bitexact=False)])
    assert rel_gate.main([path]) == 0
