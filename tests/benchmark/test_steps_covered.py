"""`steps_covered_pct` reads the loop's two counters from the process's
registry, and nothing from a program that has no such counter."""
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_metric(name):
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_steps_covered_pct_reads_the_process_registry(monkeypatch):
    from deep_vision_tpu.obs import registry as registry_mod

    reader = load_metric("steps_covered_pct")
    fresh = registry_mod.Registry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    assert reader.read({}) is None  # the parent: a program without it
    assert not fresh.metrics()      # and the reader created none
    fresh.counter("train_steps_covered_total")
    assert reader.read({}) is None  # no step yet
    fresh.counter("train_steps_total").inc(40)
    assert reader.read({}) == 0.0   # a host-bound loop
    fresh.counter("train_steps_covered_total").inc(39)
    assert reader.read({}) == 97.5


def test_the_manifest_names_the_metric_and_its_layer():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"]
              if m["name"] == "steps_covered_pct"]
    assert entry == {"name": "steps_covered_pct", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "host loop", "moves": "img_per_s_chip"}
    fetches, = [m for m in manifest["per_layer"]
                if m["name"] == "host_fetches_per_step"]
    assert entry["layer"] == fetches["layer"]
