"""Every configuration, traffic mix, cell and metric of BENCHMARK.json is
found by its name, and each cell names the files it needs."""
import importlib.util

import small  # noqa: F401  (puts bench/ on the path)
import correct
import harness
import traffic


def test_every_cell_resolves():
    spec = harness.spec()
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        c = harness.cell(w["name"])
        assert c["config"]["name"] == w["config"]
        assert configs[w["config"]]["file"] == f"bench/configs/{w['config']}.json"
        assert set(c["limits"]) <= set(correct.NUMBERS)
        assert c["mix"] == traffic.load(w["traffic"])


def test_every_metric_has_a_reader():
    spec = harness.spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_reduced_keys_are_in_the_config_file():
    for c in harness.spec()["configs"]:
        body = harness.load_json(harness.CHECKOUT / c["file"])
        assert body["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(body.get("changed_from_source", {}))
        assert body["source"] == c["source"]


def test_metrics_of_a_cell():
    name = harness.spec()["workloads"][0]["name"]
    assert "setup_s" in harness.metrics_of(name, trace=False)
    assert "step_mfu" in harness.metrics_of(name, trace=True)
    assert "setup_s" not in harness.metrics_of(name, trace=True)


def test_each_family_has_a_reference():
    for c in harness.spec()["configs"]:
        body = harness.load_json(harness.CHECKOUT / c["file"])
        path = harness.BENCH / "models" / f"{body['family']}.py"
        assert importlib.util.spec_from_file_location("m", path) is not None
