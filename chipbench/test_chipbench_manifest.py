"""``BENCHMARK.json`` against the benchmark's contract and the files it
names: character rules, metric/cell cross references, every data file
loads, every per-layer metric has a reader, and the copied fleet
builders build each configuration at a tiny size."""
import json
import math
import pathlib
import re

import numpy as np
import pytest

from chipbench import deploy

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT_FIELDS = ("why", "layer", "source")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    assert 1 <= len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(CONFIGS) == len(BENCH["configs"]) <= 24
    assert 1 <= len(CELLS) == len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_names_units_and_text():
    names = [m["name"] for m in METRICS] + list(CELLS) + list(CONFIGS)
    assert len(names) == len(set(names))
    for m in METRICS:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w[key]
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key), key
    for entry in BENCH["configs"] + BENCH["workloads"] + METRICS:
        for key in TEXT_FIELDS:
            if key in entry:
                text = entry[key]
                assert 1 <= len(text) <= 200 and "\n" not in text \
                    and "\t" not in text, text


def test_metric_entries():
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()


def _reports(cell: str, metric: dict) -> bool:
    return cell in metric.get("workloads", CELLS)


def test_cross_references():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in METRICS:
        for cell in m.get("workloads", []):
            assert cell in CELLS, (m["name"], cell)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert _reports(cell, e2e[m["moves"]]), (m["name"], cell)
    for cell in CELLS:
        got = [m for m in BENCH["end_to_end"] if _reports(cell, m)]
        assert "setup_s" in {m["name"] for m in got} and len(got) >= 2
        assert any(_reports(cell, m) for m in BENCH["per_layer"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert w["config"] in CONFIGS and w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(CONFIGS)


def test_check_budget_fits():
    """A full check of 24 cells at this run length fits its time."""
    cells, secs = 24, BENCH["run_seconds"]
    runs = 2 + 14 * cells
    assert runs * (secs + 60) + cells * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_load(cell):
    w = CELLS[cell]
    cfg = deploy.load_json("configs", w["config"])
    traffic = deploy.load_json("traffic", w["traffic"])
    spec = deploy.load_json("cells", cell)
    entry = CONFIGS[w["config"]]
    assert entry["file"] == f"chipbench/configs/{w['config']}.json"
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in cfg
    assert set(spec["limits"]) == {"cand_gap", "fold_gap", "switch_err",
                                   "failover_err", "switch_rule",
                                   "stale_active", "admit_err"}
    assert all(v >= 0 for v in spec["limits"].values())
    assert spec["probe_ticks"] <= spec["probe_span"]
    opts = deploy.pool_options(traffic, cfg["users"])
    assert 0 < opts["frame_interval_ms"] <= opts["probe_period_ms"]


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_fleet_builds_tiny(config):
    cfg = deploy.load_json("configs", config)
    cfg.update(nodes_per_metro=8, users=64)
    fleet = deploy.make_fleet(cfg, 2**31 + 5)
    n = 8 * len(cfg["metros"])
    assert len(fleet.names) == n == len(set(fleet.names))
    lo, hi = cfg["slots"]
    assert ((fleet.slots >= lo) & (fleet.slots <= hi)).all()
    spread = cfg["node_spread_deg"] + 1e-9
    centres = np.asarray(cfg["metros"])
    near = np.abs(np.stack([fleet.lat, fleet.lon], 1)[:, None, :]
                  - centres[None]).max(-1).min(1)
    assert (near <= spread).all()
    system = deploy.build_system(cfg, fleet, 2**31 + 5)
    assert len(system.am.tasks[cfg["service"]]) == n
    users = deploy.make_users(cfg, 2**31 + 5)
    assert users.shape == (64, 2) and np.isfinite(users).all()
    again = deploy.make_users(cfg, 2**31 + 5)
    assert np.array_equal(users, again)
    assert not math.isnan(float(fleet.proc_ms.mean()))


def test_refresh_policies():
    traffic = deploy.load_json("traffic", "volunteer_churn")
    assert "refresh_period_ms" not in deploy.pool_options(traffic, 1000)
    traffic["refresh"] = {"policy": "incremental", "period_probes": 20,
                          "cap_divisor": 8}
    opts = deploy.pool_options(traffic, 100_000)
    assert opts["refresh_period_ms"] == 40_000.0
    assert opts["refresh_cap"] == 12_500
    traffic["refresh"] = {"policy": "sometimes"}
    with pytest.raises(ValueError):
        deploy.pool_options(traffic, 1000)
