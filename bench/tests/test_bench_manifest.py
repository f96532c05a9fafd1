"""BENCHMARK.json resolves, keeps to its rules, and grows by files alone."""
import json
import shutil

import pytest

from bench import harness, manifest


@pytest.fixture(scope="module")
def man():
    return harness.load_manifest()


def test_manifest_keeps_its_rules(man):
    assert manifest.check(man, harness.REPO) == []


def test_every_cell_resolves_its_files(man):
    for w in man["workloads"]:
        c = harness.cell(man, w["name"])
        assert c.config["name"] == w["config"]
        assert hasattr(c.family, "init") and hasattr(c.family, "hidden")
        assert c.mix["kind"] in ("open_loop", "backlog")
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert c.per_layer and all(hasattr(c.readers[m["name"]], "read")
                                   for m in c.per_layer)
        moves = {m["name"] for m in c.end_to_end}
        assert all(m["moves"] in moves for m in c.per_layer)


def test_bad_names_are_caught(man):
    m = json.loads(json.dumps(man))
    m["per_layer"][0]["name"] = "has space"
    m["end_to_end"][0]["unit"] = "tokens per second"
    m["workloads"][0]["traffic"] = "no-such-mix"
    m["per_layer"][1]["moves"] = "not_a_metric"
    bad = manifest.check(m, harness.REPO)
    assert any("has space" in b for b in bad)
    assert any("unit" in b for b in bad)
    assert any("no-such-mix" in b for b in bad)
    assert any("moves" in b for b in bad)


def test_a_cell_added_by_files_alone(man, tmp_path):
    """A new mix, a new per-layer metric and a new cell are files and
    manifest entries; the harness finds them without an edit."""
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(harness.BENCH / d, tmp_path / "bench" / d)
    mix = json.loads((tmp_path / "bench/traffic/chat-shared-prefix.json")
                     .read_text())
    mix["arrivals"]["rate_per_s"] = 1.5
    (tmp_path / "bench/traffic/chat-slow.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/ticks_in_window.py").write_text(
        "def read(run):\n    return len(run.window_ticks()) or None\n")
    m = json.loads(json.dumps(man))
    cell = {"name": "qwen3-0.6b.chat-slow", "config": "qwen3-0.6b",
            "traffic": "chat-slow", "chips": 1, "why": "a slow chat mix"}
    m["workloads"].append(cell)
    for x in m["end_to_end"]:
        if x["name"] == "ttft_p95_ms":
            x["workloads"].append(cell["name"])
    m["per_layer"].append({"name": "ticks_in_window", "unit": "ticks",
                           "better": "higher", "source": "host_clock",
                           "layer": "scheduler", "moves": "ttft_p95_ms",
                           "workloads": [cell["name"]]})
    assert manifest.check(m, tmp_path) == []
    c = harness.cell(m, cell["name"], root=tmp_path)
    assert c.mix["arrivals"]["rate_per_s"] == 1.5
    assert {x["name"] for x in c.end_to_end} == {"ttft_p95_ms", "setup_s"}
    assert [x["name"] for x in c.per_layer] == ["ticks_in_window"]
    run = harness.Run(c, 0.0, 1.0, [], [harness.Tick(0.1, 0.2)], {}, {}, None)
    assert c.readers["ticks_in_window"].read(run) == 1
