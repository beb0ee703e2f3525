"""Flight-recorder observability suite (DESIGN.md §14).

Pins the probe contract from three sides:

  1. probes OFF is free — `simulate` returns the same SimResult bit-for-bit
     as before the probe layer existed, still compiling exactly one trace;
  2. probes ON is backend-invariant — the SimTrace is bitwise-identical
     across the ref and pallas cycle engines (the probe counters ride the
     same lane contract as the architectural counters);
  3. the run ledger and the noc_trace replay tooling round-trip.
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from repro.core.noc import sim
from repro.core.noc.sim import NoCConfig
from repro.obs import ledger, probes

TINY = dict(n_epochs=4, epoch_len=60)


def _bitwise_equal(a, b, label):
    for (path, x), (_, y) in zip(
        jax.tree_util.tree_leaves_with_path(a),
        jax.tree_util.tree_leaves_with_path(b),
    ):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{label}: leaf {jax.tree_util.keystr(path)}",
        )


# ---------------------------------------------------------------------------
# 1. probes off: zero-cost contract
# ---------------------------------------------------------------------------

def test_probes_off_result_and_trace_count_unchanged():
    """Probes on must not perturb the simulation: the SimResult is bitwise
    the probes-off result, and each static variant still compiles once."""
    cfg = NoCConfig(mode="kf", seed=2, **TINY)
    sim.reset_trace_count()
    res_off = sim.simulate(cfg, "PATH")
    assert sim.trace_count() == 1
    sim.reset_trace_count()
    res_on, trace = sim.simulate_with_trace(cfg, "PATH")
    assert sim.trace_count() == 1  # the probed variant gets its own trace
    _bitwise_equal(res_off, res_on, "probes on vs off")
    assert isinstance(trace, sim.SimTrace)


def test_probe_config_defaults_off():
    assert NoCConfig(mode="kf", **TINY).probe.enabled is False
    assert NoCConfig(mode="kf", **TINY).static_spec().probe.enabled is False


# ---------------------------------------------------------------------------
# 2. probes on: backend congruence + sanity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def probe_runs():
    cfg = NoCConfig(mode="kf", seed=0, **TINY)
    return {
        be: sim.simulate_with_trace(dataclasses.replace(cfg, backend=be),
                                    "SHIFT_PATH_BFS")
        for be in ("ref", "pallas")
    }


def test_probe_trace_ref_pallas_congruent(probe_runs):
    """SimTrace is bitwise-equal across both cycle engines."""
    res_ref, tr_ref = probe_runs["ref"]
    res_be, tr_be = probe_runs["pallas"]
    _bitwise_equal(res_ref, res_be, "SimResult ref vs pallas")
    _bitwise_equal(tr_ref, tr_be, "SimTrace ref vs pallas")


def test_probe_trace_sanity(probe_runs):
    res, tr = probe_runs["ref"]
    E, L = TINY["n_epochs"], TINY["epoch_len"]
    occ = np.asarray(tr.occ_sum)
    assert occ.shape[0] == E and occ.min() >= 0
    # per-cycle occupancy of one buffer is bounded by its depth
    assert occ.max() <= L * 64
    grant, deny = np.asarray(tr.arb_grant), np.asarray(tr.arb_deny)
    assert grant.min() >= 0 and deny.min() >= 0
    # a router has N_PORTS outputs, each granting <= 1 flit per cycle
    assert grant.max() <= L * 5
    mcq_sum, mcq_max = np.asarray(tr.mcq_sum), np.asarray(tr.mcq_max)
    assert mcq_sum.min() >= 0 and mcq_max.min() >= 0
    assert (mcq_max <= mcq_sum).all()  # max over cycles <= sum over cycles
    assert np.isfinite(np.asarray(tr.kf_gain)).all()
    assert np.isfinite(np.asarray(tr.kf_cov_trace)).all()
    # the emitted signal IS the binarized one-step prediction
    np.testing.assert_array_equal(
        (np.asarray(tr.kf_x_pred) > 0.0).astype(np.int32),
        np.asarray(res.kf_signal),
    )
    summary = probes.summarize_trace(tr)
    assert summary["epochs"] == E
    assert summary["occ_sum_total"] == int(occ.sum())


# ---------------------------------------------------------------------------
# 3. run ledger: schema + append round-trip
# ---------------------------------------------------------------------------

def test_ledger_probe_row_round_trip(tmp_path):
    bench = tmp_path / "BENCH_noc.json"
    rec = {"bench": "noc_obs", "timestamp": "2026-01-01T00:00:00",
           "backend": "cpu", "probe_overhead_steady": 1.1}
    ledger.append(dict(rec), path=str(bench))
    rows = json.loads(bench.read_text())
    assert len(rows) == 1
    row = rows[0]
    # append stamps provenance and the result validates as a stamped row
    assert row["ledger_version"] == ledger.LEDGER_VERSION
    assert set(ledger.STAMP_FIELDS) <= set(row)
    assert ledger.validate_row(row) == []
    # the JSONL mirror carries the same record
    mirror = tmp_path / "LEDGER_noc.jsonl"
    assert json.loads(mirror.read_text().splitlines()[-1]) == row
    # second append extends the array (and keeps it valid JSON)
    ledger.append(dict(rec), path=str(bench))
    assert len(json.loads(bench.read_text())) == 2


def test_ledger_probe_rejects_bad_rows(tmp_path):
    bench = tmp_path / "BENCH_noc.json"
    with pytest.raises(ValueError):
        ledger.append({"timestamp": "t", "backend": "cpu"}, path=str(bench))
    with pytest.raises(ValueError):
        ledger.append({"bench": 7, "timestamp": "t", "backend": "cpu"},
                      path=str(bench))
    assert not bench.exists()  # invalid rows never reach the file
    # legacy (unstamped) rows are tolerated by validate, future versions not
    legacy = {"bench": "b", "timestamp": "t", "backend": "cpu"}
    assert ledger.validate_row(legacy) == []
    future = dict(legacy, ledger_version=ledger.LEDGER_VERSION + 1,
                  git_sha="x", device_kind="cpu")
    assert ledger.validate_row(future) != []


def test_ledger_mirror_append_is_atomic(tmp_path):
    """The JSONL mirror is rewritten via temp file + os.replace, and a torn
    (non-newline-terminated) tail line left by a crashed writer is dropped
    instead of being glued onto the next row."""
    bench = tmp_path / "BENCH_noc.json"
    mirror = tmp_path / "LEDGER_noc.jsonl"
    rec = {"bench": "noc_obs", "timestamp": "t1", "backend": "cpu"}
    ledger.append(dict(rec), path=str(bench))
    with open(mirror, "a") as f:
        f.write('{"bench": "torn')  # crashed writer: partial, no newline
    ledger.append(dict(rec, timestamp="t2"), path=str(bench))
    rows = [json.loads(line) for line in mirror.read_text().splitlines()]
    assert [r["timestamp"] for r in rows] == ["t1", "t2"]
    assert not (tmp_path / "LEDGER_noc.jsonl.tmp").exists()


def test_ledger_mirror_retries_once_on_oserror(tmp_path, monkeypatch):
    """One transient OSError on the atomic rename is absorbed; a second
    consecutive failure propagates."""
    bench = tmp_path / "BENCH_noc.json"
    rec = {"bench": "noc_obs", "timestamp": "t1", "backend": "cpu"}
    real_replace = os.replace
    fails = {"left": 1}

    def flaky(src, dst):
        if fails["left"] > 0:
            fails["left"] -= 1
            raise OSError("transient")
        return real_replace(src, dst)

    monkeypatch.setattr(ledger.os, "replace", flaky)
    ledger.append(dict(rec), path=str(bench))
    mirror = tmp_path / "LEDGER_noc.jsonl"
    assert len(mirror.read_text().splitlines()) == 1

    fails["left"] = 2  # both attempts fail -> the error surfaces
    with pytest.raises(OSError):
        ledger.append(dict(rec, timestamp="t2"), path=str(bench))


def test_ledger_probe_config_hash_stable():
    cfg = NoCConfig(mode="kf", **TINY)
    h1 = ledger.config_hash(cfg)
    assert h1 == ledger.config_hash(NoCConfig(mode="kf", **TINY))
    assert h1 != ledger.config_hash(
        dataclasses.replace(cfg, seed=cfg.seed + 1))


# ---------------------------------------------------------------------------
# 4. noc_trace replay tooling
# ---------------------------------------------------------------------------

def test_noc_trace_probe_capture_round_trip(tmp_path, probe_runs):
    from benchmarks import noc_trace

    res, tr = probe_runs["ref"]
    cap = {f: np.asarray(v) for f, v in zip(sim.SimTrace._fields, tr)}
    cap["kf_signal"] = np.asarray(res.kf_signal)
    cap["applied_config"] = np.asarray(res.applied_config)
    cap["gpu_ipc"] = np.asarray(res.gpu_ipc)
    cap["avg_latency"] = np.asarray(res.avg_latency)
    cap.update(workload="SHIFT_PATH_BFS", mode="kf",
               n_epochs=TINY["n_epochs"], epoch_len=TINY["epoch_len"],
               seed=0, backend="ref")
    path = str(tmp_path / "cap.npz")
    noc_trace.save(cap, path)
    cap2 = noc_trace.load(path)
    for k, v in cap.items():
        np.testing.assert_array_equal(np.asarray(cap2[k]), np.asarray(v),
                                      err_msg=f"round-trip: {k}")
    ascii_lines = noc_trace.render_ascii(cap2)
    csv_lines = noc_trace.render_csv(cap2)
    assert len(ascii_lines) == TINY["n_epochs"] + 2
    assert len(csv_lines) == TINY["n_epochs"] + 1
    assert all("," in ln for ln in csv_lines)
