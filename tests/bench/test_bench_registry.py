"""bench/registry.py finds a cell's pieces by name: adding a
configuration, a mix, a driver or a metric is adding files."""
import json
import types

from bench import registry


def test_new_pieces_are_found_as_files_only(tmp_path):
    for sub in ("configs", "mixes", "drivers", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "caps-new.json").write_text(
        json.dumps({"network": "Caps-New", "num_h_caps": 7}))
    (tmp_path / "mixes" / "bursty.json").write_text(
        json.dumps({"driver": "burst", "rate_per_s": 10}))
    (tmp_path / "drivers" / "burst.py").write_text(
        "def run(ctx):\n    return ('burst', ctx)\n")
    (tmp_path / "metrics" / "queue_wait.py").write_text(
        "def read(outcome, ctx):\n    return outcome.counters['wait']\n")
    (tmp_path / "metrics" / "hits.special.py").write_text(
        "def read(outcome, ctx):\n    return 99.0\n")

    assert registry.config("caps-new", tmp_path)["num_h_caps"] == 7
    mix = registry.mix("bursty", tmp_path)
    assert registry.driver(mix["driver"], tmp_path).run(1) == ("burst", 1)
    outcome = types.SimpleNamespace(counters={"wait": 3.5})
    # a suffixed name falls back to the reader of its quantity
    assert registry.metric_reader("queue_wait.tail", tmp_path)(
        outcome, None) == 3.5
    assert registry.metric_reader("queue_wait", tmp_path)(
        outcome, None) == 3.5
    # a reader of its own takes precedence
    assert registry.metric_reader("hits.special", tmp_path)(
        outcome, None) == 99.0


def test_every_cell_of_the_benchmark_resolves():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        cfg = registry.config(w["config"])
        mix = registry.mix(w["traffic"])
        assert callable(registry.driver(mix["driver"]).run)
        assert cfg["chips"] == w["chips"]
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))
