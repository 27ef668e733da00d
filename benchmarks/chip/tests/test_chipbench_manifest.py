"""BENCHMARK.json's names and units keep to their character sets, every
part a cell names exists as a file of its own, and a configuration, a
traffic mix or a metric added as new files is found without an edit."""
import json
import os
import re
import shutil

import chipbench_tiny  # noqa: F401  (paths)
import pytest

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    return harness.manifest()


def test_names_and_units(man):
    names = [c["name"] for c in man["configs"]]
    names += [w["name"] for w in man["workloads"]]
    names += [w["config"] for w in man["workloads"]]
    names += [w["traffic"] for w in man["workloads"]]
    names += [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    names += [k for c in man["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in man["workloads"]}) == len(
        man["workloads"])


def test_every_part_is_a_file(man):
    for c in man["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        cfg = harness.load_json("configs", c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    for w in man["workloads"]:
        tr = harness.load_json("traffic", w["traffic"])
        assert os.path.exists(os.path.join(harness.HERE, "drivers",
                                           tr["driver"] + ".py"))
        assert harness.load_json("limits", w["name"])
        assert os.path.exists(os.path.join(harness.HERE, "reference",
                                           w["config"] + ".py"))
        # every cell reports setup_s, another end-to-end metric and a
        # per-layer metric
        e2e = [m["name"] for m in harness.metrics_for(man, w["name"],
                                                      "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(man, w["name"], "per_layer")
    for m in man["per_layer"]:
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in [e["name"] for e in man["end_to_end"]]


def test_added_files_are_found_without_an_edit(tmp_path, man):
    """A copy of the benchmark gains a configuration, a mix and a metric
    as new files and manifest entries only; the harness finds all three."""
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    cfg = harness.load_json("configs", "granite-moe-1b-a400m-2l")
    cfg["name"] = "granite-moe-1b-a400m-4l"
    cfg["num_hidden_layers"] = 4
    cfg["model"]["n_layers"] = 4
    (bench / "configs" / "granite-moe-1b-a400m-4l.json").write_text(
        json.dumps(cfg))
    tr = harness.load_json("traffic", "fedagrac-kasync")
    tr["k_seed"] = 11
    (bench / "traffic" / "fedagrac-kasync-b.json").write_text(json.dumps(tr))
    (bench / "metrics" / "chunks_per_window.py").write_text(
        "def read(ctx):\n    return ctx.values.get('chunks')\n")
    (bench / "limits" / "granite4-kasync-b.json").write_text(
        json.dumps({"loss_gap": 1.0}))
    new = json.loads(json.dumps(man))
    new["configs"].append({**man["configs"][0],
                           "name": "granite-moe-1b-a400m-4l"})
    new["workloads"].append({"name": "granite4-kasync-b",
                             "config": "granite-moe-1b-a400m-4l",
                             "traffic": "fedagrac-kasync-b", "chips": 1,
                             "why": "a cell added as data"})
    new["per_layer"].append({"name": "chunks_per_window", "unit": "1",
                             "better": "higher", "source": "host_clock",
                             "layer": "round step",
                             "moves": "train_tokens_per_s",
                             "workloads": ["granite4-kasync-b"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    got = harness.manifest(str(root))
    cell = harness.find_cell(got, "granite4-kasync-b")
    assert harness.load_json("configs", cell["config"], str(bench))[
        "num_hidden_layers"] == 4
    assert harness.load_json("traffic", cell["traffic"], str(bench))[
        "k_seed"] == 11
    per_layer = [m["name"] for m in harness.metrics_for(
        got, "granite4-kasync-b", "per_layer")]
    assert per_layer == ["chunks_per_window"]
    ctx = harness.Context(workload="granite4-kasync-b", cell=cell,
                          config={}, traffic={}, limits={}, seed=1,
                          seconds=1, trace=True, t0=0.0, base=str(bench))
    ctx.values["chunks"] = 3
    mod = harness.load_module("metrics", "chunks_per_window", str(bench))
    assert mod.read(ctx) == 3
    # the original cells still resolve as before
    assert [m["name"] for m in harness.metrics_for(
        got, "granite-fedagrac-kasync", "per_layer")] == [
        m["name"] for m in harness.metrics_for(
            man, "granite-fedagrac-kasync", "per_layer")]
