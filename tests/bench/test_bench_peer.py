"""The expert-parallel cell on the CPU: its plain reference against the
program, the configuration against DeepSeek-V2-Lite's published numbers,
the harness end to end, and what the cell's check catches.

The run here is the `noc6x6-moe-ep` configuration (the 6x6 package, 64
routed experts on its 14 GPU tiles) cut to 4 epochs of 40 cycles, on the
dense engine, under the cell's traffic.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.noc import engine, sim, topology
from repro.core.noc import traffic as prog_traffic

from bench import run
from bench.entries import peer as entry
from bench.entries import sweep as sweep_entry
from bench.reference import noc, noc_peer

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.prefill4k"
CONFIG = json.loads((ROOT / "bench/configs/noc6x6-moe-ep.json").read_text())
TRAFFIC = json.loads((ROOT / "bench/traffic/prefill4k.json").read_text())
SEED = 2**31 + 41
POINTS = entry.sweep.grid(TRAFFIC)


def tiny_config(n_epochs=4, epoch_len=40) -> dict:
    cfg = json.loads(json.dumps(CONFIG))
    cfg["name"] = "tiny"
    cfg["engine"] = "ref"
    cfg["noc"].update(n_epochs=n_epochs, epoch_len=epoch_len)
    return cfg


@pytest.fixture(scope="module")
def runner():
    return entry.Runner(tiny_config(), TRAFFIC, chips=1, seed=SEED)


@pytest.fixture(scope="module")
def step(runner):
    return runner.step(1)


@pytest.mark.parametrize("mode", TRAFFIC["modes"])
def test_reference_matches_the_program(runner, step, mode):
    idx = [i for i, p in enumerate(POINTS) if p["mode"] == mode][::2]
    got = jax.device_get([step.rows[i] for i in idx])
    want = runner.reference([(step.seed, i) for i in idx])
    for i, a, b in zip(idx, got, want):
        bad, gap = entry.compare(a, b)
        assert bad == 0 and gap <= sweep_entry.FLOAT_GAP_LIMIT, POINTS[i]
        # the dispatches flow and are served at the expert tiles
        assert b["gpu_peer_push"].sum() > 0 and b["peer_served"].sum() > 0
        assert b["peer_served"].sum() <= b["gpu_peer_push"].sum()
        assert b["moved"].min() > 0, POINTS[i]


def test_the_configuration_is_deepseek_v2_lite_on_the_package():
    """The file's model numbers are the published config's; the peer table
    is the round-robin placement of its 64 routed experts over the GPU
    tiles in router order; the package is `noc6x6`'s."""
    base = json.loads((ROOT / "bench/configs/noc6x6.json").read_text())
    noc_block = dict(CONFIG["noc"])
    weights = noc_block.pop("peer_weights")
    assert noc_block == base["noc"] and CONFIG["reduced"] == []
    assert (CONFIG["n_routed_experts"], CONFIG["num_experts_per_tok"],
            CONFIG["n_shared_experts"], CONFIG["hidden_size"],
            CONFIG["moe_intermediate_size"], CONFIG["kv_lora_rank"],
            CONFIG["q_lora_rank"]) == (64, 6, 2, 2048, 1408, 512, None)
    topo = topology.make_topology(6, 6, 8)
    gpus = np.flatnonzero(topo.node_type == topology.NT_GPU)
    want = np.zeros(36, int)
    for e in range(CONFIG["n_routed_experts"]):
        want[gpus[e % len(gpus)]] += 1
    assert weights == want.tolist()
    assert sorted(set(weights) - {0}) == [4, 5] and sum(weights) == 64


def test_gpu_peer_share_follows_its_formula():
    """The traffic's share is D / (D + M) of the configuration's `assumed`,
    recomputed from the published widths, to three decimals."""
    c = CONFIG
    experts, k, hidden = c["n_routed_experts"], c["num_experts_per_tok"], \
        c["hidden_size"]
    heads, tokens, bf16 = c["num_attention_heads"], 4096, 2
    h = experts / 14
    dispatch = k * (experts - h) / experts * hidden * bf16
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    mla = (hidden * heads * qk + hidden * (c["kv_lora_rank"]
           + c["qk_rope_head_dim"]) + c["kv_lora_rank"] * heads
           * (c["qk_nope_head_dim"] + c["v_head_dim"])
           + heads * c["v_head_dim"] * hidden) * bf16
    gate = experts * hidden * bf16
    weights = ((h + c["n_shared_experts"]) * 3 * hidden
               * c["moe_intermediate_size"] * bf16 + mla + gate)
    kv = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * bf16
    memory = weights / tokens + kv
    assert mla == 27_525_120 and kv == 1152
    share = round(dispatch / (dispatch + memory), 3)
    assert share == 0.39
    fig9 = json.loads((ROOT / "bench/traffic/fig9.json").read_text())
    assert TRAFFIC["modes"] == fig9["modes"]
    for wl, rates in TRAFFIC["workloads"].items():
        assert rates == dict(fig9["workloads"][wl], gpu_peer_share=share)


def test_the_bfloat16_control_differs(runner):
    pairs = [(7, i) for i in (1, 10, 19)]
    verdict = entry.judge([entry.compare(a, b) for a, b in zip(
        runner.reference(pairs, lowp=True), runner.reference(pairs))])
    assert not verdict["correct"]
    assert verdict["compared"]["float_rel_gap"][0] > \
        sweep_entry.FLOAT_GAP_LIMIT


def test_peer_edges_leave_the_source_out():
    w = [0, 2, 0, 1, 3]
    edges, total = noc_peer.peer_edges(w)
    assert edges.tolist() == [[0, 2, 2, 3, 6], [0, 0, 0, 1, 4],
                              [0, 2, 2, 3, 6], [0, 2, 2, 2, 5],
                              [0, 2, 2, 3, 3]]
    assert total.tolist() == [6, 4, 6, 5, 3]


# ---- the harness end to end, and what the check catches


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with one more cell: the tiny configuration
    under the cell's traffic."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(tiny_config()))
    spec["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny.json", "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "prefill4k", "chips": 1,
                              "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_tiny(root):
    return run.run_cell(root, CELL, SEED, 0.2, False, require_tpu=False)


def test_the_cell_is_correct_at_a_tiny_size(root):
    out = run_tiny(root)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] % 24 == 0 and out["attempted"] >= 24
    assert set(out["metrics"]) == {"router_cycles_per_s", "setup_s"}
    assert out["compared"]["int_mismatches"] == {
        "value": 0, "limit": sweep_entry.INT_LIMIT}


def _to_mcs(real):
    # peers sent to an MC: every workload runs with no dispatch share
    def sweep(specs, **kw):
        out = []
        for s in specs:
            rates = prog_traffic.lookup_workload(s.workload)._replace(
                gpu_peer_share=0.0)
            prog_traffic.register_workload(s.workload + ".mc", rates,
                                           overwrite=True)
            out.append(s._replace(workload=s.workload + ".mc"))
        return real(out, **kw)
    return sweep


def _no_servers(real):
    # the server role left off: the expert tiles neither take nor serve
    def sweep(specs, **kw):
        jax.clear_caches()
        return real(specs, **kw)
    return sweep


def _miscounted(real):
    # peer_served miscounted: one more in the first epoch of every point
    def sweep(specs, **kw):
        return [r._replace(counters=r.counters._replace(
            peer_served=r.counters.peer_served.at[0].add(1)))
            for r in real(specs, **kw)]
    return sweep


@pytest.mark.parametrize("fault", [_to_mcs, _no_servers, _miscounted],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_deployment_is_not_correct(root, monkeypatch, fault):
    if fault is _no_servers:
        real_fabric = sim.Fabric
        monkeypatch.setattr(sim, "Fabric", lambda **kw: real_fabric(
            **dict(kw, is_server=kw["is_mc"])))
    monkeypatch.setattr(sim, "sweep", fault(sim.sweep))
    try:
        out = run_tiny(root)
    finally:
        jax.clear_caches()   # no trace of a broken program outlives the test
    assert out["correct"] is False
    assert out["compared"]["int_mismatches"]["value"] > 0
    assert out["failed"] > 0


def test_compare_counts_the_peer_counters():
    want = {k: np.arange(4, dtype=np.int32) for k in noc_peer.INTS}
    want.update({k: np.float32([0.5, 2.0, 0.0, 1.0]) for k in noc.FLOATS})
    assert entry.compare(want, want) == (0, 0.0)
    got = dict(want, peer_served=np.int32([0, 1, 2, 4]))
    assert entry.compare(got, want) == (1, 0.0)
    got = {k: v for k, v in want.items() if k != "gpu_peer_push"}
    assert entry.compare(got, want)[0] == 4
    # a program row of a peer run reads by the same names
    r = sim.SimResult(*(np.zeros(3, np.float32) for _ in range(5)),
                      counters=engine.PeerCounters(*(
                          np.zeros(3, np.int32) for _ in noc_peer.INTS[3:])),
                      gpu_inj_rate=np.zeros(3, np.float32),
                      gpu_vc_quota=np.zeros(3, np.int32))
    assert set(sweep_entry.readings(r)) == set(noc_peer.INTS + noc.FLOATS)


def test_the_new_reader_reads_nothing_without_its_label():
    """A trace of a program without the `epoch.dest` label (a v5e trace of
    the MC-only program) gives no value and raises nothing."""
    import gzip
    from types import SimpleNamespace as NS

    from bench import trace_reduce

    reader = run.load_module(ROOT / "bench/metrics/epoch_dest_ns.py",
                             "test_peer_epoch_dest_ns")
    pd = jax.profiler.ProfileData.from_serialized_xspace(gzip.decompress(
        (ROOT / "bench/testdata/labelled_v5e.xplane.pb.gz").read_bytes()))
    red = trace_reduce.reduce_profile(pd, "bench.window", n_devices=1)
    ctx = run.Context(trace=red, steps=[NS(point_cycles=24 * 8)],
                      monitor=run.Monitor(), setup_span=(0.0, 1.0))
    assert reader.read(ctx) is None
    ctx.trace = None
    assert reader.read(ctx) is None

