"""BENCHMARK.json against the files the harness finds by name."""

import json
import os
import re
import shutil

import pytest

from chipbench import harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


def test_names_units_and_bounds_keep_to_the_contract():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics] + WORKLOADS + list(CONFIGS)
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert all(re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
               for m in metrics)
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(WORKLOADS) // 2)
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_workload_resolves_to_its_files(name):
    cell = harness.load_cell(name)
    assert cell.clients >= 1 and cell.tokens_per_round > 0
    assert cell.limits is not None, f"no chipbench/limits/{name}.json"
    assert set(cell.limits["limits"]) == {"loss_gap", "grad_gap",
                                          "grad_err", "update_gap"}
    assert harness.family(cell.config).loss
    for metric in cell.per_layer:
        assert callable(harness.metric_reader(metric))
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("name", list(CONFIGS))
def test_a_configuration_is_the_registry_entry_but_for_reduced(name):
    from repro.configs import REGISTRY
    entry = CONFIGS[name]
    cfg = json.load(open(os.path.join(harness.ROOT, entry["file"])))
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert entry["source"] == cfg["source"]
    reg = REGISTRY[cfg["registry"]]
    allowed = set(cfg["reduced"]) | set(cfg.get("registry_differs", {}))
    for key, value in cfg["model"].items():
        want = getattr(reg, key)
        if key == "ssm":
            want = {k: getattr(want, k) for k in value}
        if key not in allowed:
            assert value == want, (key, value, want)
        else:
            assert value != want, key


def test_new_files_are_found_without_editing_the_harness(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", ".*"))
    bench = json.loads(json.dumps(BENCH))
    src = harness._read_json(os.path.join(harness.ROOT,
                                          CONFIGS["mamba2-370m"]["file"]))
    src["name"] = "mamba2-370m-l2"
    src["model"]["num_layers"] = 2
    (root / "chipbench/configs/mamba2-370m-l2.json").write_text(
        json.dumps(src))
    traffic = harness._read_json(os.path.join(
        harness.ROOT, "chipbench/traffic/c2.t256.json"))
    traffic["seq_len"] = 128
    (root / "chipbench/traffic/c2.t128.json").write_text(json.dumps(traffic))
    (root / "chipbench/metrics/rounds_read.py").write_text(
        "def read(ctx):\n    return float(ctx.rounds)\n")
    bench["configs"].append(dict(CONFIGS["mamba2-370m"], name="mamba2-370m-l2",
                                 file="chipbench/configs/mamba2-370m-l2.json"))
    bench["workloads"].append({"name": "mamba2-370m-l2.c2.t128",
                               "config": "mamba2-370m-l2",
                               "traffic": "c2.t128", "chips": 1,
                               "why": "a new cell"})
    bench["per_layer"].append({"name": "rounds_read", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "tokens_per_s",
                               "workloads": ["mamba2-370m-l2.c2.t128"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("mamba2-370m-l2.c2.t128", root=str(root))
    assert cell.config["model"]["num_layers"] == 2
    assert cell.traffic["seq_len"] == 128
    assert "rounds_read" in cell.per_layer
    reader = harness.metric_reader("rounds_read", root=str(root))
    assert reader(type("Ctx", (), {"rounds": 7})()) == 7.0
    assert harness.family(cell.config, str(root)).flops_per_token(
        cell.config["model"], 128) > 0
    # the cells that were there still load as before
    old = harness.load_cell(WORKLOADS[0], root=str(root))
    assert "rounds_read" not in old.per_layer
